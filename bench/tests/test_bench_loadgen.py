"""The traffic generator: deterministic per seed, the same requests in the
same order at the same arrival times for every seed (the seed draws only
token ids), due times anchored on the window's opening."""
import json
from pathlib import Path

import pytest

from bench import loadgen

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
VOCAB = 151936
BIG_SEED = 2 ** 31 + 12345


def mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def sizes(items):
    """The request sizes of a plan, in order."""
    out = []
    for it in items:
        p = it.payload
        if "query" in p:
            out.append(p["query"])
        elif "suffixes" in p:
            out.append((len(p["prefix"]),
                        tuple(len(s) for s in p["suffixes"])))
        else:
            out.append((len(p["prompt"]), p["max_tokens"]))
    return out


@pytest.mark.parametrize("name", ["long.steady", "long.saturated",
                                  "fanout.steady", "chat.saturated"])
def test_plan_is_deterministic_and_the_seed_only_reorders(name):
    t = mix(name)
    a = loadgen.plan(t, BIG_SEED, 40, VOCAB)
    b = loadgen.plan(t, BIG_SEED, 40, VOCAB)
    c = loadgen.plan(t, 3, 40, VOCAB)
    assert [i.payload for i in a] == [i.payload for i in b]
    assert [i.due_s for i in a] == [i.due_s for i in b]
    # another seed: the same requests in the same order, other token ids
    assert sizes(a) == sizes(c)
    if t["requests"]["kind"] == "pice":
        assert [i.payload for i in a] == [i.payload for i in c]
    else:
        assert [i.payload for i in a] != [i.payload for i in c]
    # the same arrival times (open loop) whatever the seed
    assert [i.due_s for i in a] == [i.due_s for i in c]


@pytest.mark.parametrize("name", ["long.steady", "fanout.steady"])
def test_open_loop_due_times_are_anchored_on_the_window(name):
    t = mix(name)
    rate = t["arrival"]["rate_per_s"]
    items = loadgen.plan(t, BIG_SEED, 40, VOCAB)
    due = [i.due_s for i in items]
    assert due[0] == 0.0
    assert due == sorted(due) and due[-1] < 40
    assert len(items) == round(rate * 40)


def test_fanout_sizes_follow_the_pipeline_rule():
    t = mix("fanout.steady")
    r = t["requests"]
    for it in loadgen.plan(t, 5, 40, VOCAB):
        p = it.payload
        assert r["prefix_tokens"][0] <= len(p["prefix"]) <= \
            r["prefix_tokens"][1]
        assert r["groups"][0] <= len(p["suffixes"]) <= r["groups"][1]
        longest = max(len(s) for s in p["suffixes"])
        assert p["max_new"] == int(3.5 * longest) + 24
        ids = p["prefix"] + [x for s in p["suffixes"] for x in s]
        assert min(ids) >= 1 and max(ids) <= 255


def test_chat_ids_cover_the_vocabulary_but_end_of_sequence():
    t = mix("chat.saturated")
    items = loadgen.plan(t, 9, 40, VOCAB)
    ids = [x for it in items[:64] for x in it.payload["prompt"]]
    assert min(ids) >= 1 and max(ids) < VOCAB and max(ids) > 255
    lens = [len(it.payload["prompt"]) for it in items]
    assert 128 <= min(lens) and max(lens) <= 1024
