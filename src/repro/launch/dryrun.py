"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this script builds the real step function (train / prefill /
decode), lowers it under the production mesh with explicit in/out
shardings, compiles it, and records:

  * ``memory_analysis()``   — per-device bytes (proves the cell fits HBM)
  * ``cost_analysis()``     — HLO FLOPs / bytes-accessed (roofline terms)
  * collective bytes        — parsed from the optimized HLO text: summed
    operand bytes of all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute ops (cost_analysis does not report these)

Artifacts land in experiments/dryrun/<arch>__<shape>__<mesh>.json; the
roofline reader (benchmarks/roofline.py) consumes them.

Usage:
  python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k --mesh both
  python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""

import argparse
import dataclasses
import json
import os
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs as cfgs
from repro.distributed.specs import batch_pspecs, cache_pspecs, opt_pspecs
from repro.launch.mesh import make_production_mesh
from repro.models import LM, param_pspecs, param_shape_structs
from repro.models.params import param_counts
from repro.optim import adafactor, adamw
from repro.train.steps import make_train_step

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "experiments", "dryrun")

# microbatch accumulation per (arch family size): bounds activation peak
ACCUM = {"nemotron-4-340b": 8, "deepseek-v3-671b": 8, "qwen2-72b": 4,
         "qwen2.5-32b": 4, "llava-next-34b": 4, "recurrentgemma-9b": 2}

# >=30B params: Adafactor (factored 2nd moment); else AdamW
ADAFACTOR_ARCHS = {"qwen2-72b", "qwen2.5-32b", "nemotron-4-340b",
                   "llava-next-34b", "deepseek-v3-671b"}

# Winning per-arch settings from the Sec-Perf hillclimb (EXPERIMENTS.md):
# act: residual-stream sharding mode; group: 2-level remat group size;
# accum: microbatch count override; moe_cf: MoE capacity factor override.
OPT_SETTINGS = {
    "qwen2-72b": {"act": "sp"},
    "deepseek-v3-671b": {"moe_cf": 1.0},
    "nemotron-4-340b": {"group": 8, "accum": 16},
}


def apply_opt(arch: str) -> None:
    o = OPT_SETTINGS.get(arch, {})
    os.environ["REPRO_ACT_SHARDING"] = o.get("act", "baseline")
    os.environ["REPRO_REMAT_GROUP"] = str(o.get("group", 1))
    if "accum" in o:
        ACCUM[arch] = o["accum"]
    if "moe_cf" in o:
        # the override is read by build_cell from the environment
        os.environ["REPRO_MOE_CF"] = str(o["moe_cf"])
    else:
        os.environ.pop("REPRO_MOE_CF", None)

_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*"
                     r"((?:\([^)]*\))|(?:\S+))\s+([\w\-]+)")
_SHAPE_RE = re.compile(r"(f32|f16|bf16|s32|u32|s16|u16|s8|u8|pred|f64|c64)"
                       r"\[([0-9,]*)\]")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_BYTES = {"f64": 8, "c64": 8, "f32": 4, "s32": 4, "u32": 4, "f16": 2,
          "bf16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1}
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(shape_text: str) -> float:
    nbytes = 0.0
    for dt, dims in _SHAPE_RE.findall(shape_text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        nbytes += n * _BYTES[dt]
    return nbytes


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Per-device bytes moved by collectives in the optimized (partitioned)
    HLO.  For each collective op we count max(result bytes, operand bytes):
    result-dominant for all-gather, operand-dominant for reduce-scatter,
    equal for all-reduce / all-to-all / collective-permute.  Async pairs
    count once (the -start; -done is skipped)."""
    defs: dict[str, float] = {}
    out: dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, shape_text, opname = m.groups()
        defs[name] = _shape_bytes(shape_text)
        kind = next((c for c in _COLLECTIVES if opname.startswith(c)), None)
        if kind is None or opname.endswith("-done"):
            continue
        args_text = line[m.end():]
        args_text = args_text.split("metadata=")[0].split("replica_groups=")[0]
        operand_bytes = sum(defs.get(nm, 0.0)
                            for nm in _OPERAND_RE.findall(args_text))
        out[kind] = out.get(kind, 0.0) + max(defs[name], operand_bytes)
    return out


def _tree_bytes(sds_tree) -> int:
    tot = 0
    for leaf in jax.tree_util.tree_leaves(sds_tree):
        n = 1
        for d in leaf.shape:
            n *= d
        tot += n * jnp.dtype(leaf.dtype).itemsize
    return tot


def analytic_memory(cfg, sh, mesh, accum, p_sds, opt_sds, cache_sds) -> dict:
    """Per-chip residency model for the TPU target (HLO `temp` on the CPU
    backend over-reports: xla:cpu upcasts bf16 math to f32 and hoists
    whole-stack converts out of scan loops — see EXPERIMENTS.md §Dry-run).

    params/opt: template bytes / (tp x fsdp);  grads: one more param copy;
    activations: saved scan carries (n_layers x microbatch x S x d) x1.5
    for per-block extras;  cache: sharded decode cache.
    """
    tp = mesh.shape["model"]
    dp = mesh.size // tp
    fsdp = mesh.shape["data"] if cfg.param_dtype == "bfloat16" else 1
    shard = tp * fsdp
    out = {"params": _tree_bytes(p_sds) / shard}
    out["opt"] = _tree_bytes(opt_sds) / shard if opt_sds is not None else 0.0
    out["grads"] = out["params"]
    if sh.kind == "train":
        mb = max(sh.global_batch // (dp * accum), 1)
        act = 2  # bf16 activations
        layers = cfg.n_layers + cfg.encoder_layers
        out["activations"] = 1.5 * layers * mb * sh.seq_len * cfg.d_model * act
    else:
        out["grads"] = 0.0
        mb = max(sh.global_batch // dp, 1)
        out["activations"] = 3 * mb * sh.seq_len * cfg.d_model * 2 \
            if sh.kind == "prefill" else 0.0
    out["cache"] = _tree_bytes(cache_sds) / mesh.size if cache_sds is not None else 0.0
    out["total"] = sum(out.values())
    out = {k: float(v) for k, v in out.items()}
    out["fits_16gb"] = bool(out["total"] < 16 * 2 ** 30)
    return out


def _named(tree_pspec, mesh):
    return jax.tree_util.tree_map(
        lambda ps: NamedSharding(mesh, ps), tree_pspec,
        is_leaf=lambda x: isinstance(x, P))


def build_cell(arch: str, shape_name: str, mesh):
    """Returns (fn, args_sds, in_shardings, out_shardings, donate, extras)."""
    cfg = cfgs.get_config(arch)
    if os.environ.get("REPRO_MOE_CF") and cfg.moe is not None:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=float(os.environ["REPRO_MOE_CF"])))
    sh = cfgs.SHAPES[shape_name]
    model = LM(cfg)
    tp = mesh.shape["model"]
    fsdp = mesh.shape["data"] if cfg.param_dtype == "bfloat16" else 0
    p_ps = param_pspecs(cfg, fsdp_size=fsdp, tp_size=tp)
    p_sds = param_shape_structs(cfg)
    mesh_axes = tuple(mesh.axis_names)

    if sh.kind == "train":
        opt = (adafactor(1e-4) if arch in ADAFACTOR_ARCHS else adamw(1e-4))
        step_fn = make_train_step(LM(cfg), opt, accum_steps=ACCUM.get(arch, 1))
        batch_sds = cfgs.input_specs(cfg, sh)
        opt_sds = jax.eval_shape(opt.init, p_sds)
        o_ps = opt_pspecs(opt_sds, p_ps)
        b_ps = batch_pspecs(batch_sds, mesh_axes)
        in_sh = (_named(p_ps, mesh), _named(o_ps, mesh), _named(b_ps, mesh),
                 NamedSharding(mesh, P()))
        out_sh = (_named(p_ps, mesh), _named(o_ps, mesh), None)
        args = (p_sds, opt_sds, batch_sds,
                jax.ShapeDtypeStruct((), jnp.int32))
        extras = {"opt_sds": opt_sds, "cache_sds": None,
                  "accum": ACCUM.get(arch, 1)}
        return step_fn, args, in_sh, out_sh, (0, 1), extras

    if sh.kind == "prefill":
        batch_sds = cfgs.input_specs(cfg, sh)
        b_ps = batch_pspecs(batch_sds, mesh_axes)

        def prefill_fn(params, batch):
            return model.prefill(params, batch, max_len=sh.seq_len + 128)

        cache_sds = jax.eval_shape(prefill_fn, p_sds, batch_sds)[0]
        c_ps = cache_pspecs(cfg, cache_sds, mesh_axes, tp, sh.global_batch)
        in_sh = (_named(p_ps, mesh), _named(b_ps, mesh))
        out_sh = (_named(c_ps, mesh), None)
        extras = {"opt_sds": None, "cache_sds": cache_sds, "accum": 1}
        return prefill_fn, (p_sds, batch_sds), in_sh, out_sh, (), extras

    # decode: one token against a seq_len cache
    def init_cache():
        return model.init_cache(sh.global_batch, sh.seq_len)

    cache_sds = jax.eval_shape(init_cache)
    if cfg.is_encdec:  # decode against encoder memory
        enc_sds = jax.ShapeDtypeStruct(
            (sh.global_batch, 4096, cfg.d_model), cfg.activation_dtype)
        cache_sds = dict(cache_sds, enc_out=enc_sds)
    c_ps = cache_pspecs(cfg, cache_sds, mesh_axes, tp, sh.global_batch)
    tok_sds = cfgs.input_specs(cfg, sh)["tokens"]
    b_ps = batch_pspecs({"tokens": tok_sds}, mesh_axes)["tokens"]

    def decode_fn(params, cache, tokens):
        return model.decode_step(params, cache, tokens)

    in_sh = (_named(p_ps, mesh), _named(c_ps, mesh), NamedSharding(mesh, b_ps))
    out_sh = (None, _named(c_ps, mesh))
    extras = {"opt_sds": None, "cache_sds": cache_sds, "accum": 1}
    return decode_fn, (p_sds, cache_sds, tok_sds), in_sh, out_sh, (1,), extras


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             save: bool = True, art_dir: str | None = None) -> dict:
    mesh_name = "multi" if multi_pod else "single"
    cell_id = f"{arch}__{shape_name}__{mesh_name}"
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    fn, args, in_sh, out_sh, donate, extras = build_cell(arch, shape_name, mesh)
    from repro.distributed.compat import enter_mesh
    enter_mesh(mesh)   # context mesh: makes with_sharding_constraint live
    with mesh:
        lowered = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                          donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        coll = collective_bytes(compiled.as_text())
    # XLA cost_analysis counts while/scan bodies ONCE; our jaxpr walker
    # multiplies by trip counts, giving exact *global* FLOPs.  The ratio
    # (jaxpr_flops/devices) / hlo_flops is the scan-correction factor we
    # apply to the (same-shaped) bytes and collective estimates.
    from repro.core.profiler import flops_by_category, traffic_bytes
    with mesh:
        jcat = flops_by_category(fn, *args)
        jbytes = traffic_bytes(fn, *args)
    jflops = sum(v for k, v in jcat.items() if not k.startswith("__"))
    hlo_flops = float(cost.get("flops", 0.0))
    scan_corr = (jflops / mesh.size) / hlo_flops if hlo_flops > 0 else 1.0
    scan_corr = max(scan_corr, 1.0)

    cfg = cfgs.get_config(arch)
    total_p, active_p = param_counts(cfg)
    analytic = analytic_memory(cfg, cfgs.SHAPES[shape_name], mesh,
                               extras["accum"], args[0], extras["opt_sds"],
                               extras["cache_sds"])
    record = {
        "cell": cell_id, "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "devices": int(mesh.size),
        "flops": hlo_flops,
        "jaxpr_flops_global": float(jflops),
        "jaxpr_flops_by_category": {k: float(v) for k, v in jcat.items()},
        "scan_correction": float(scan_corr),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "bytes_accessed_corrected": float(cost.get("bytes accessed", 0.0))
        * float(scan_corr),
        "jaxpr_traffic_bytes_global": float(jbytes),
        "collective_bytes": coll,
        "collective_bytes_total": float(sum(coll.values())),
        "collective_bytes_corrected": float(sum(coll.values())) * float(scan_corr),
        "argument_bytes_per_device": int(mem.argument_size_in_bytes),
        "output_bytes_per_device": int(mem.output_size_in_bytes),
        "temp_bytes_per_device": int(mem.temp_size_in_bytes),
        "alias_bytes_per_device": int(mem.alias_size_in_bytes),
        # residency estimate: args + outputs + temps - aliased (donated) pairs
        "peak_bytes_per_device": int(mem.argument_size_in_bytes
                                     + mem.output_size_in_bytes
                                     + mem.temp_size_in_bytes
                                     - mem.alias_size_in_bytes),
        "analytic_memory_per_device": analytic,
        "params_total": total_p, "params_active": active_p,
        "accum_steps": extras["accum"],
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
    }
    print(f"[dryrun] {cell_id}: flops={record['flops']:.3e} "
          f"bytes={record['bytes_accessed']:.3e} "
          f"coll={record['collective_bytes_total']:.3e} "
          f"peak/dev={(record['peak_bytes_per_device'] or 0)/2**30:.2f}GiB "
          f"(lower {t_lower:.1f}s compile {t_compile:.1f}s)")
    if save:
        d = art_dir or ARTIFACT_DIR
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, cell_id + ".json"), "w") as f:
            json.dump(record, f, indent=1)
    return record


def all_cells() -> list[tuple[str, str]]:
    out = []
    for arch in cfgs.ARCHS:
        fam = cfgs.get_config(arch).family
        for shape_name in cfgs.applicable_shapes(fam):
            out.append((arch, shape_name))
    return out


def main() -> None:
    # 512 placeholder CPU devices for the production meshes; read by the
    # backend at its first use, which no import above triggers
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=512").strip()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--opt", action="store_true",
                    help="optimized activation sharding (REPRO_ACT_SHARDING=dp)")
    ap.add_argument("--outdir", default=None)
    args = ap.parse_args()
    if args.opt:
        if args.outdir is None:
            args.outdir = os.path.join(os.path.dirname(ARTIFACT_DIR),
                                       "dryrun_opt")
    cells = all_cells() if args.all else [(args.arch, args.shape)]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    failures = []
    for arch, shape_name in cells:
        for multi in meshes:
            cell_id = f"{arch}__{shape_name}__{'multi' if multi else 'single'}"
            path = os.path.join(args.outdir or ARTIFACT_DIR, cell_id + ".json")
            if args.skip_existing and os.path.exists(path):
                print(f"[dryrun] {cell_id}: cached, skipping")
                continue
            try:
                if args.opt:
                    apply_opt(arch)
                run_cell(arch, shape_name, multi, art_dir=args.outdir)
            except Exception as e:
                traceback.print_exc()
                failures.append((cell_id, repr(e)))
    if failures:
        print(f"\n[dryrun] {len(failures)} FAILED cells:")
        for cid, err in failures:
            print(f"  {cid}: {err[:200]}")
        raise SystemExit(1)
    print("\n[dryrun] all requested cells compiled OK")


if __name__ == "__main__":
    main()
