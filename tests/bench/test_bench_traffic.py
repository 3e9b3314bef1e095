"""The seeded traffic generator: deterministic, and the same work per seed,
in the same order."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

import traffic  # noqa: E402

BIG = 2**31 + 12345


FIX = Path(__file__).resolve().parent / "fixtures"


def mix(name):
    return traffic.load_mix(ROOT / "bench" / "traffic" / f"{name}.json")


def test_open_schedule_is_deterministic():
    a = traffic.open_schedule(mix("chat"), BIG, 20.0, 65024, 10.0)
    b = traffic.open_schedule(mix("chat"), BIG, 20.0, 65024, 10.0)
    assert [(r.due, r.max_tokens, r.prompt) for r in a] == \
        [(r.due, r.max_tokens, r.prompt) for r in b]


def test_every_seed_gets_the_same_work_in_another_order():
    m = mix("chat")
    a = [r for r in traffic.open_schedule(m, 1, 20.0, 65024, 10.0) if r.counted]
    b = [r for r in traffic.open_schedule(m, BIG, 20.0, 65024, 10.0) if r.counted]
    assert len(a) == len(b) == round(m["rate_rps"] * 20.0)
    # the same lengths and arrivals, request by request; the seed orders
    # only the prompts' tokens
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.max_tokens for r in a] == [r.max_tokens for r in b]
    assert [r.due for r in a] == [r.due for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    # the one shared order is a shuffle, not the sorted quantiles
    plens = [len(r.prompt) for r in a]
    assert plens != sorted(plens)
    # the counted arrivals span the window exactly
    assert abs(a[-1].due - 20.0) < 1e-9 and abs(b[-1].due - 20.0) < 1e-9


def test_lengths_follow_the_mix():
    m = mix("chat")
    lens = traffic.quantile_lengths(m["prompt"], 1001)
    assert lens.min() >= 32 and lens.max() <= 3072
    assert np.median(lens) == 512
    out = traffic.quantile_lengths({"dist": "uniform", "min": 256, "max": 768}, 4)
    assert list(out) == [320, 448, 576, 704]


def test_a_mix_other_than_an_open_loop_is_refused(tmp_path):
    bad = dict(mix("chat"), loop="closed")
    (tmp_path / "m.json").write_text(json.dumps(bad))
    with pytest.raises(ValueError, match="open"):
        traffic.load_mix(tmp_path / "m.json")


def test_every_request_fits_its_engine():
    for path in (ROOT / "bench" / "traffic").glob("*.json"):
        m = traffic.load_mix(path)
        eng = m["engine"]
        assert traffic.longest_sequence(m) <= eng["max_len"]
        assert -(-traffic.longest_sequence(m) // 16) <= eng["num_blocks"] - 1
        json.dumps(m)
