"""The schedule is a pure function of (traffic file, seconds); the seed
fills in contents only."""

import json
import os

import pytest

from chipbench import traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def mix(name):
    with open(os.path.join(ROOT, "chipbench", "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["chat-steady", "batch-closed-32"])
def test_two_seeds_same_schedule_one_seed_same_bytes(name):
    tr = mix(name)
    slots = traffic.schedule(tr, 50.0)
    again = traffic.schedule(tr, 50.0)
    assert slots == again
    a = traffic.Filler(tr, 32768, 7).fill(slots)
    b = traffic.Filler(tr, 32768, 2 ** 31 + 9).fill(slots)
    a2 = traffic.Filler(tr, 32768, 7).fill(traffic.schedule(tr, 50.0))
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert [(len(r.prompt), r.max_new_tokens) for r in a] == \
        [(len(r.prompt), r.max_new_tokens) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert json.dumps([(r.due_s, r.prompt, r.max_new_tokens) for r in a]) \
        == json.dumps([(r.due_s, r.prompt, r.max_new_tokens) for r in a2])
    shared = tr["shared_prompts"]["tokens"]
    for r in a:
        assert len(r.prompt) == shared + r.slot.unique_len
        assert tr["unique_tokens"]["lo"] <= r.slot.unique_len \
            <= tr["unique_tokens"]["hi"]
        assert tr["output_tokens"]["lo"] <= r.max_new_tokens \
            <= tr["output_tokens"]["hi"]
        assert all(0 <= t < 32768 for t in r.prompt)


def test_open_loop_fills_the_window_and_prerolls():
    tr = mix("chat-steady")
    slots = traffic.schedule(tr, 50.0)
    win = [s for s in slots if s.due_s >= 0]
    pre = [s for s in slots if s.due_s < 0]
    assert len(win) == round(tr["rate_per_s"] * 50.0)
    assert len(pre) == round(tr["rate_per_s"] * tr["preroll_s"])
    assert all(0 <= s.due_s < 50.0 for s in win)
    assert all(-tr["preroll_s"] <= s.due_s < 0 for s in pre)
    assert [s.due_s for s in slots] == sorted(s.due_s for s in slots)
    # the count follows the seconds, the lengths' distribution does not
    assert len([s for s in traffic.schedule(tr, 25.0) if s.due_s >= 0]) \
        == round(tr["rate_per_s"] * 25.0)


@pytest.mark.parametrize("which", ["unique_len", "out_len"])
def test_every_block_of_eight_holds_one_length_from_each_octile(which):
    tr = mix("chat-steady")
    tr["rate_per_s"], tr["preroll_s"] = 2.0, 0.0
    slots = traffic.schedule(tr, 64.0)          # 128 requests, 16 blocks
    vals = [getattr(s, which) for s in slots]
    ranked = sorted(vals)
    n_blocks = len(vals) // traffic.BLOCK
    cuts = [ranked[j * n_blocks] for j in range(traffic.BLOCK)] + \
        [ranked[-1] + 1]
    for b in range(n_blocks):
        block = sorted(vals[b * 8:(b + 1) * 8])
        for j, v in enumerate(block):
            assert cuts[j] <= v <= cuts[j + 1], (b, j, v)
    # and all the blocks together are exactly the quantiles
    key = "unique_tokens" if which == "unique_len" else "output_tokens"
    assert ranked == list(traffic._quantiles(tr[key], len(vals)))


def test_closed_loop_stream_goes_on_in_cycles():
    tr = mix("batch-closed-32")
    first = traffic.schedule(tr, 50.0)
    assert len(first) == tr["clients"]
    assert all(-tr["stagger_s"] <= s.due_s < 0 for s in first)
    filler = traffic.Filler(tr, 32000, 5)
    stream = traffic.Stream(tr, filler, len(first))
    nxt = [stream.take() for _ in range(tr["cycle"])]
    assert [r.slot.index for r in nxt] == \
        list(range(len(first), len(first) + tr["cycle"]))
    lens = [(s.unique_len, s.out_len) for s in
            traffic.more_slots(tr, 0, tr["cycle"])]
    for r in nxt:
        assert (r.slot.unique_len, r.slot.out_len) == \
            lens[r.slot.index % tr["cycle"]]


def test_packed_batches_differ_row_by_row():
    tr = mix("packed-4k")
    a = traffic.packed_batches(tr, 32768, 3, 1)
    b = traffic.packed_batches(tr, 32768, 3, 1)
    assert len(a) == tr["pool"]
    assert all((x[0] == y[0]).all() for x, y in zip(a, b))
    assert len({x[0].tobytes() for x in a}) == tr["pool"]
    assert (a[0][1][:, :-1] == a[0][0][:, 1:]).all()
