"""The traffic generator: deterministic per seed, different across seeds,
and the same set of sizes and arrivals for every seed."""
import numpy as np
import pytest

from bench import cells, traffic

MIXES = ["decode_b16", "decode_b48", "prefill_b16", "chat_poisson"]
BIG = 2 ** 40 + 12345


def _closed(plan, n=40):
    return [plan.closed_request(c, k) for k in range(n // plan.clients + 1)
            for c in range(plan.clients)][:n]


def _requests(mix, seed):
    plan = traffic.Plan(mix, 32000, seed)
    return plan, (_closed(plan) if plan.loop == "closed"
                  else plan.open_requests(60.0))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = cells.load_traffic(name)
    _, a = _requests(mix, BIG)
    _, b = _requests(mix, BIG)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.max_new == y.max_new and x.due == y.due
        assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_differ(name):
    mix = cells.load_traffic(name)
    _, a = _requests(mix, 1)
    _, b = _requests(mix, 2)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    if "size_seed" in mix:    # the same sizes in the same order
        assert [x.max_new for x in a] == [y.max_new for y in b]
        assert [len(x.prompt) for x in a] == [len(y.prompt) for y in b]
    else:
        assert [x.max_new for x in a] != [y.max_new for y in b]


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_draws_one_pool(name):
    """The seed only reorders the stratified pools of sizes and gaps."""
    mix = cells.load_traffic(name)
    p1, p2 = traffic.Plan(mix, 100, 3), traffic.Plan(mix, 100, BIG)
    assert sorted(p1._prompt) == sorted(p2._prompt)
    assert sorted(p1._output) == sorted(p2._output)
    assert p1._prompt.min() >= mix["prompt"]["min"]
    assert p1._prompt.max() <= mix["prompt"]["max"]
    if mix["loop"] == "open":
        assert p1._arrivals[-1] == pytest.approx(p2._arrivals[-1])
        assert p1._arrivals[-1] == pytest.approx(
            traffic.POOL / mix["rate_per_s"], rel=0.02)


@pytest.mark.parametrize("name", MIXES)
def test_requests_fit_the_engine(name):
    mix = cells.load_traffic(name)
    plan = traffic.Plan(mix, 100, 5)
    assert plan.max_seq_len() <= mix["engine"]["max_seq_len"]
    if mix["loop"] == "closed":
        # every slot can hold its longest sequence at once: no eviction
        pages = -(-plan.max_seq_len() // mix["engine"]["page_size"])
        assert mix["engine"]["num_pages"] >= mix["engine"]["max_batch"] * pages
        assert mix["clients"] <= mix["engine"]["max_batch"]


@pytest.mark.parametrize("block", [16, 48, 100])
def test_blocks_serve_the_same_sizes(block):
    """With ``block``, every whole block of requests holds the same
    sizes for every seed, in an order the seed draws."""
    mix = dict(cells.load_traffic("prefill_b16"), block=block)
    mix.pop("size_seed", None)
    p1, p2 = traffic.Plan(mix, 100, 3), traffic.Plan(mix, 100, BIG)
    assert len(p1._prompt) % block == 0
    assert traffic.POOL <= len(p1._prompt) < traffic.POOL + block
    for a, b in ((p1._prompt, p2._prompt), (p1._output, p2._output),
                 (p1._first, p2._first)):
        for k in range(0, len(a), block):
            assert sorted(a[k:k + block]) == sorted(b[k:k + block])
        assert not np.array_equal(a[:block], b[:block])
    open_mix = dict(cells.load_traffic("chat_poisson"), block=block)
    o1, o2 = traffic.Plan(open_mix, 100, 3), traffic.Plan(open_mix, 100, BIG)
    assert o1._arrivals[block - 1] == pytest.approx(o2._arrivals[block - 1])
    assert not np.allclose(o1._arrivals[:block - 1], o2._arrivals[:block - 1])


def test_size_seed_fixes_the_sizes_not_the_tokens():
    mix = dict(cells.load_traffic("decode_b16"), size_seed=7)
    a, b, c = (traffic.Plan(mix, 1000, s) for s in (1, BIG, 7))
    ra, rb = a.closed_request(3, 2), b.closed_request(3, 2)
    assert ra.max_new == rb.max_new and len(ra.prompt) == len(rb.prompt)
    assert not np.array_equal(ra.prompt, rb.prompt)
    plain = traffic.Plan(cells.load_traffic("decode_b16"), 1000, 7)
    assert np.array_equal(c._prompt, plain._prompt)
    assert np.array_equal(a._output, plain._output)
