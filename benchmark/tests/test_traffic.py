import json
import os

import pytest

from benchmark.harness import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spec(**kw):
    s = traffic.load_traffic(BENCH, "mixtral-8x7b-d6.chat-over", "chat-over")
    s.update(kw)
    return s


def key(reqs):
    return [(round(r.due_s, 9), tuple(r.prompt_ids), r.max_tokens) for r in reqs]


def test_cell_file_extends_the_shape():
    s = traffic.load_traffic(BENCH, "mixtral-8x7b-d6.chat-over", "chat-over")
    assert s["file"] == "traffic/mixtral-8x7b-d6.chat-over.json"
    assert s["prompt_tokens"]["median"] == 256 and s["rate_rps"] > 0


@pytest.mark.parametrize("seed", [1, 3000000019, 2**31 + 5])
def test_same_seed_same_schedule(seed):
    a = traffic.schedule(spec(), seed, 50, 32768, 2048, 4)
    b = traffic.schedule(spec(), seed, 50, 32768, 2048, 4)
    assert key(a) == key(b) and len(a) > 10


def test_another_seed_differs_but_offers_the_same_work():
    a = traffic.schedule(spec(), 1, 50, 32768, 2048, 4)
    b = traffic.schedule(spec(), 2, 50, 32768, 2048, 4)
    assert key(a) != key(b)
    assert len(a) == len(b)
    # the same prompt lengths and gaps in another order; an output is only
    # ever cut where its prompt leaves no room
    assert [r.due_s for r in a] != [r.due_s for r in b]
    assert sorted(len(r.prompt_ids) for r in a) == sorted(
        len(r.prompt_ids) for r in b)
    assert abs(sum(r.max_tokens for r in a) - sum(r.max_tokens for r in b)) \
        <= 0.02 * sum(r.max_tokens for r in a)


def test_lengths_within_their_clips_and_context():
    reqs = traffic.schedule(spec(rate_rps=20), 7, 50, 32768, 2048, 4)
    assert len(reqs) > 500
    for r in reqs:
        assert 16 <= len(r.prompt_ids) <= 1536
        assert 16 <= r.max_tokens <= 512
        assert len(r.prompt_ids) + r.max_tokens + 4 <= 2048
        assert 0 <= r.due_s < 50
        assert all(8 <= t < 32768 for t in r.prompt_ids)
    assert [r.due_s for r in reqs] == sorted(r.due_s for r in reqs)
    med = sorted(len(r.prompt_ids) for r in reqs)[len(reqs) // 2]
    assert 200 <= med <= 320
    long_share = sum(len(r.prompt_ids) > 512 for r in reqs) / len(reqs)
    assert 0.1 < long_share < 0.3          # chunked prefill is exercised


def test_rate_is_met():
    reqs = traffic.schedule(spec(rate_rps=3.0), 11, 50, 32768, 2048, 4)
    assert abs(len(reqs) - 150) <= 2


def test_no_two_prompts_share_a_prefix():
    reqs = traffic.schedule(spec(), 5, 50, 32768, 2048, 4)
    heads = {tuple(r.prompt_ids[:8]) for r in reqs}
    assert len(heads) == len(reqs)


def test_fields_later_cells_need_are_read():
    shared = traffic.schedule(spec(shared_prefix_tokens=64), 5, 20, 32768, 2048, 4)
    # a prompt's length includes the shared part; a shorter one is its head
    assert len({tuple(r.prompt_ids[:64]) for r in shared
                if len(r.prompt_ids) >= 64}) == 1
    burst = traffic.schedule(spec(burst={"size_min": 8, "size_max": 24,
                                         "within_s": 0.2}), 5, 50, 32768, 2048, 4)
    gaps = sorted(b.due_s - a.due_s for a, b in zip(burst, burst[1:]))
    assert gaps[len(gaps) // 2] < 0.05      # most arrivals sit inside a burst
    sess = traffic.schedule(spec(sessions={"turns_min": 3, "turns_max": 6,
                                           "think_s": 2.0}), 5, 50, 32768, 2048, 4)
    later = [r for r in sess if r.turn > 0]
    assert later and all(r.session >= 0 for r in later)
    mix = traffic.schedule(spec(prompt_tokens={"dist": "mixture", "parts": [
        {"weight": 9, "dist": "lognormal", "median": 256, "sigma": 0.5,
         "min": 16, "max": 1024},
        {"weight": 1, "dist": "fixed", "value": 1500}]}), 5, 50, 32768, 2048, 4)
    share = sum(len(r.prompt_ids) == 1500 for r in mix) / len(mix)
    assert 0.05 < share < 0.15


@pytest.mark.parametrize("rate", [1.125, 2.53125])
def test_any_stretch_offers_the_same_work_whatever_the_seed(rate):
    """Blocks of balanced lengths: the tokens asked for by the first four
    fifths of the arrivals differ by a few percent between seeds (a plain
    shuffle: 10-20% between the extremes of 20 seeds)."""
    heads = []
    for seed in range(20):
        reqs = traffic.schedule(spec(rate_rps=rate), seed, 50, 32768, 1536, 4)
        head = reqs[:len(reqs) * 4 // 5]
        heads.append((sum(r.max_tokens for r in head),
                      sum(len(r.prompt_ids) for r in head)))
        heads[-1] += (sum(r.due_s < 20 for r in reqs),)
    for k in (0, 1):
        v = [h[k] for h in heads]
        assert (max(v) - min(v)) / min(v) < 0.06
    early = [h[2] for h in heads]       # arrivals in the first 20 s
    assert max(early) - min(early) <= 5 and abs(early[0] - rate * 20) <= 4


def test_the_warm_up_is_another_stretch_of_traffic():
    a = traffic.schedule(spec(), 1, 50, 32768, 1536, 4)
    w = traffic.schedule(spec(), 1 ^ 0xA5A5A5, 4, 32768, 1536, 4)
    assert w and [r.prompt_ids for r in w] != [r.prompt_ids for r in a[:len(w)]]
