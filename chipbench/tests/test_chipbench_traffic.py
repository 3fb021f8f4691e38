"""The traffic generator: deterministic per seed, and the same work under
every seed (stratified lengths and gaps, shuffled)."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"
BIG = 2**40 + 12345


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


@pytest.mark.parametrize("seed", [1, BIG])
def test_open_loop_deterministic(seed):
    a = traffic.open_loop(mix("chat-poisson"), 20, seed, 64000)
    b = traffic.open_loop(mix("chat-poisson"), 20, seed, 64000)
    assert [(r.uid, r.prompt, r.max_new, r.due_s) for r in a] == \
        [(r.uid, r.prompt, r.max_new, r.due_s) for r in b]


def shuffled(name):
    """The mix with its schedule shuffled by the run's seed."""
    m = mix(name)
    m.pop("order_seed", None)
    return m


def test_open_loop_same_work_other_order():
    m = shuffled("chat-poisson")
    a = traffic.open_loop(m, 20, 3, 64000)
    b = traffic.open_loop(m, 20, BIG, 64000)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert a[0].prompt != b[0].prompt


def test_open_loop_rate_and_bounds():
    m = mix("chat-poisson")
    reqs = traffic.open_loop(m, 20, 9, 64000)
    rate = m["arrival"]["rate_per_s"]
    assert len(reqs) == round(rate * 20)
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 20
    p, o = m["prompt_tokens"], m["output_tokens"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in reqs)
    assert all(o["min"] <= r.max_new <= o["max"] for r in reqs)
    assert all(1 <= t < 64000 for r in reqs for t in r.prompt)
    # the median of the stratified lengths is the mix's median
    assert abs(np.median([len(r.prompt) for r in reqs]) - p["median"]) < 30


def test_closed_loop_clients_and_cycles():
    m = mix("rag-closed")
    a, b = traffic.ClosedLoop(m, 5, 32064), traffic.ClosedLoop(m, 5, 32064)
    seq_a = [a.next(c % 8) for c in range(128)]
    seq_b = [b.next(c % 8) for c in range(128)]
    assert [(r.uid, r.prompt, r.max_new) for r in seq_a] == \
        [(r.uid, r.prompt, r.max_new) for r in seq_b]
    assert {r.uid for r in seq_a} == set(range(128))
    # each cycle of 64 holds the same lengths, shuffled
    cyc = lambda reqs, c: sorted(len(r.prompt) for r in reqs
                                 if r.uid // 64 == c)
    assert cyc(seq_a, 0) == cyc(seq_a, 1)
    assert max(len(r.prompt) + r.max_new for r in seq_a) <= 2047


def test_every_mix_fits_its_cells():
    """No request of a mix outgrows the cache of a cell that serves it."""
    root = MIXES.parent
    for path in (root / "workloads").glob("*.json"):
        cell = json.loads(path.read_text())
        assert traffic.longest(mix(cell["traffic"])) <= \
            cell["engine"]["max_seq"] - 1, path.name


@pytest.mark.parametrize("name", ["chat-poisson", "rag-closed"])
def test_order_seed_replays_one_schedule(name):
    """With ``order_seed`` every run seed gets the same lengths, in the same
    order, at the same due times; only the token ids differ."""
    m = dict(mix(name), order_seed=77)
    vocab = 64000
    if m["loop"] == "open":
        a = traffic.open_loop(m, 20, 3, vocab)
        b = traffic.open_loop(m, 20, BIG, vocab)
    else:
        la, lb = traffic.ClosedLoop(m, 3, vocab), traffic.ClosedLoop(m, BIG, vocab)
        a = [la.next(c % 8) for c in range(80)]
        b = [lb.next(c % 8) for c in range(80)]
    assert [(r.uid, len(r.prompt), r.max_new, r.due_s) for r in a] == \
        [(r.uid, len(r.prompt), r.max_new, r.due_s) for r in b]
    assert all(x.prompt != y.prompt for x, y in zip(a, b))
