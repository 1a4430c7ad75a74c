"""Each generator: the same seed gives the same requests, another seed gives
others, and every seed gets the same amount of work."""

import importlib.util
import json
import pathlib

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))
BIG = 2**31 + 12345


def states_order(mix_name):
    """Whether the mix draws the order of its sizes from a number of its
    own (``order_seed``) and not from the run's seed."""
    mix = json.loads((BENCH / "traffic" / f"{mix_name}.json").read_text())
    return "order_seed" in mix["params"]


def plan_of(mix_name, seed, seconds=6.0):
    mix = json.loads((BENCH / "traffic" / f"{mix_name}.json").read_text())
    spec = importlib.util.spec_from_file_location(
        mix["kind"], BENCH / "generators" / f"{mix['kind']}.py")
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    params = dict(mix["params"], **mix["rehearse"]["params"])
    return gen.plan(params, seed, seconds, 512)


def requests_of(plan):
    if plan["mode"] == "open":
        return plan["setup"] + plan["requests"]
    return plan["setup"] + [r for s in plan["clients"] for r in s]


def same(a, b):
    return (len(a) == len(b) and all(
        x["due_s"] == y["due_s"] and x["max_new"] == y["max_new"]
        and np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b)))


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests_other_seed_others(mix):
    a, b, c = (requests_of(plan_of(mix, s)) for s in (BIG, BIG, BIG + 1))
    assert same(a, b) and not same(a, c)
    for r in a:
        assert r["prompt"].dtype == np.int32 and r["prompt"].min() >= 1
        assert r["prompt"][-1] != 0 and r["max_new"] >= 1


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_work(mix):
    a, b = plan_of(mix, 7), plan_of(mix, BIG)

    def sizes(plan):
        """Answers, documents (each asked about several times), and what a
        request brings besides its document."""
        rs = requests_of(plan)
        docs = [r["tags"]["document_tokens"] for r in rs
                if r["tags"].get("new_document")] + [
            len(r["prompt"]) for r in rs if r["tags"].get("setup")]
        return (sorted(r["max_new"] for r in rs), sorted(docs), sorted(
            len(r["prompt"]) - r["tags"].get("document_tokens", 0)
            for r in rs if not r["tags"].get("setup")))

    assert sizes(a) == sizes(b)
    if a["mode"] == "open":
        assert len(a["requests"]) == len(b["requests"])
        assert sorted(r["due_s"] for r in a["requests"]) != \
            sorted(r["due_s"] for r in b["requests"])
        assert max(r["due_s"] for r in a["requests"]) < 6.0


@pytest.mark.parametrize("mix", MIXES)
def test_the_seed_draws_the_order_unless_the_mix_states_it(mix):
    a, b = requests_of(plan_of(mix, 7)), requests_of(plan_of(mix, BIG))
    sizes = [[(len(r["prompt"]), r["max_new"]) for r in rs] for rs in (a, b)]
    if states_order(mix):
        # One schedule for every seed; the seed still draws every token.
        assert sizes[0] == sizes[1]
        assert not any(np.array_equal(x["prompt"], y["prompt"])
                       for x, y in zip(a, b))
    else:
        assert sizes[0] != sizes[1]


def test_order_seed_alone_draws_the_order_of_a_closed_loop():
    mix = json.loads((BENCH / "traffic" / "docqa.json").read_text())
    spec = importlib.util.spec_from_file_location(
        "gen", BENCH / "generators" / "closed_loop_docs.py")
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    params = dict(mix["params"], **mix["rehearse"]["params"])

    def sizes(p, seed):
        return [(len(r["prompt"]), r["max_new"])
                for r in requests_of(gen.plan(p, seed, 6.0, 512))]

    stated = dict(params, order_seed=3)
    assert sizes(stated, 1) == sizes(stated, BIG)
    assert sizes(stated, 1) != sizes(dict(params, order_seed=4), 1)
    free = {k: v for k, v in params.items() if k != "order_seed"}
    assert sizes(free, 1) != sizes(free, BIG)
    assert sorted(sizes(free, 1)) != [] and \
        sorted(m for _, m in sizes(free, 1)) == \
        sorted(m for _, m in sizes(stated, 1))


def test_open_loop_ramp_is_set_up_traffic_of_the_same_rate():
    plan = plan_of("chat", 5)
    ramp = [r for r in plan["requests"] if r["tags"].get("ramp")]
    window = [r for r in plan["requests"] if not r["tags"].get("ramp")]
    assert ramp and all(-1.5 <= r["due_s"] < 0 for r in ramp)
    assert all(0 <= r["due_s"] < 6.0 for r in window)
    assert [r["due_s"] for r in plan["requests"]] == \
        sorted(r["due_s"] for r in plan["requests"])
    assert len(ramp) == round(4.0 * 1.5) and len(window) == round(4.0 * 6.0)


def test_closed_loop_documents_are_asked_again():
    plan = plan_of("docqa", 11)
    for stream, first in zip(plan["clients"], plan["setup"]):
        doc = first["prompt"]
        fresh = [r["request"] if "request" in r else r for r in stream]
        assert np.array_equal(fresh[0]["prompt"][:len(doc)], doc)
        new = [r["tags"]["new_document"] for r in stream]
        assert not new[0] and 0.2 <= sum(new) / len(new) <= 0.3
