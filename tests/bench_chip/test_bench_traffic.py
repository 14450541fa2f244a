"""The traffic generator: the same seed gives the same traffic, every
seed sends the same sizes in another order, and no frame repeats within
a run; the fft cell's sample spreads over the whole window."""

import numpy as np
import pytest

from benchmarks.chip import common, traffic

BIG = 2**33 + 12345          # a seed wider than 32 bits
GRATINGS = {"kind": "gratings", "count": 3, "max_cycles": 24,
            "amp_min": 0.12, "amp_max": 0.2, "mean": 0.5, "noise": 0.2}


def test_frames_deterministic_per_seed(spec=GRATINGS):
    a = traffic.make_frames(spec, BIG, 3, 2, (32, 24))
    b = traffic.make_frames(spec, BIG, 3, 2, (32, 24))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    c = traffic.make_frames(spec, BIG + 2**32, 3, 2, (32, 24))
    assert not np.array_equal(a[0], c[0])       # the high word counts


def test_frames_never_repeat_within_a_run(spec=GRATINGS):
    seen = set()
    for index in range(6):
        for f in traffic.make_frames(spec, BIG, index, 4, (16, 16)):
            f = np.asarray(f)
            assert f.min() >= 0.0 and f.max() <= 1.0
            seen.add(f.tobytes())
    assert len(seen) == 24


def test_unknown_frame_kind_is_an_error():
    with pytest.raises(common.BenchError):
        traffic.make_frames({"kind": "uniform"}, BIG, 0, 1, (8, 8))


CHAT = {"pool": 256, "block": 32,
        "prompt": {"median": 1020, "sigma": 0.6, "min": 256, "max": 1536,
                   "multiple": 256},
        "output": {"median": 129, "sigma": 0.6, "min": 16, "max": 384}}


def test_chat_same_sizes_other_order():
    a = traffic.chat_requests(CHAT, BIG)
    assert a == traffic.chat_requests(dict(CHAT), BIG)
    c = traffic.chat_requests(CHAT, 99)
    assert a != c and len(a) == len(c) == 256
    # every block of 32 requests holds the same sizes under every seed
    for k in range(0, 256, 32):
        assert sorted(p for p, _ in a[k:k + 32]) == \
            sorted(p for p, _ in c[k:k + 32])
        assert sorted(n for _, n in a[k:k + 32]) == \
            sorted(n for _, n in c[k:k + 32])
    prompts = {p for p, _ in a}
    assert prompts <= {256, 512, 768, 1024, 1280, 1536}
    assert all(16 <= n <= 384 for _, n in a)
    assert all(p + n < 2048 for p, n in a)
    assert np.median([p for p, _ in a]) == 1024
    assert np.median([n for _, n in a]) == pytest.approx(129, abs=1)


def test_burst_sample_spreads_over_the_window():
    """Each burst of a window is as likely as any other to be compared:
    over many seeds the kept bursts average the window's middle."""
    from benchmarks.chip.drivers.offload import reservoir_slot
    bursts, keep, kept = 600, 6, []
    for seed in range(200):
        rng = common.np_rng(BIG + seed, 5)
        held = []
        for b in range(bursts):
            slot = reservoir_slot(rng, b, keep)
            if slot < len(held):
                held[slot] = b
            elif slot < keep:
                held.append(b)
        assert len(set(held)) == keep
        kept += held
    assert np.mean(kept) == pytest.approx(bursts / 2, rel=0.05)
    assert np.mean(np.array(kept) >= bursts * 2 // 3) == \
        pytest.approx(1 / 3, abs=0.05)


def test_prompt_tokens_deterministic_and_distinct():
    a = traffic.prompt_tokens(BIG, 4, 64, 1000)
    assert a == traffic.prompt_tokens(BIG, 4, 64, 1000)
    assert a != traffic.prompt_tokens(BIG, 5, 64, 1000)
    assert all(0 <= t < 1000 for t in a)


def test_seed_words_keep_the_high_bits():
    assert common.seed_words(2**33 + 7) == (7, 2)
    with pytest.raises(common.BenchError):
        common.seed_words(-1)
