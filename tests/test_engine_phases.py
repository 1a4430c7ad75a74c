"""What the serving path says about itself (PR 24): ``kft.*`` named scopes
in the AOT programs (models/generate.py), ``kft.engine.*`` phase
annotations and the cumulative ``loop_*_s`` / queue-wait / prefill-span /
compile counters of ``DecodeEngine`` (serving/engine.py).  CPU, the tiny LM
the other engine tests run, and (PR 26) the same LM as a looped stack:
two loop steps over its two layers, sandwich norms, so that every path the
engine sizes by the pool's leading axis runs with 4 planes for 2 layers."""

import dataclasses
import re
import threading
import time

import numpy as np
import pytest

SEED = 20260927
VOCAB, NEW_TOKENS = 128, 12
SCOPES = {"kft.embed", "kft.qkv_proj", "kft.kv_write", "kft.kv_view",
          "kft.attention", "kft.attn_out", "kft.mlp", "kft.logits",
          "kft.sample"}
# Top-level phases of one iteration in the order they may be entered;
# wait_work lies inside admit, a blocking token read inside drain is a
# round_wait of its own, and every round_wait holds one round_read (PR 39).
RANK = {"admit": 0, "housekeeping": 1, "prefill_dispatch": 2,
        "round_prepare": 3, "round_dispatch": 4, "overlap": 5,
        "round_wait": 6, "drain": 7, "account": 8}
NESTED = {"wait_work": "admit", "round_wait": "drain",
          "round_read": "round_wait"}
PREFIX = "kft.engine."


LOOPED = {"loop_steps": 2, "sandwich_norm": True}


def _stack(**widths):
    """``(cfg, params, decode)`` of the tiny LM, with ``widths`` over its
    own."""
    import jax
    from flax import linen as nn

    from kubeflow_tpu.models.generate import DecodeConfig
    from kubeflow_tpu.models.transformer import Transformer
    from kubeflow_tpu.serving.loaders import _model_config

    cfg = _model_config({
        "vocab_size": VOCAB, "d_model": 32, "n_layers": 2, "n_heads": 4,
        "n_kv_heads": 2, "d_ff": 64, "head_dim": 8, "max_seq_len": 64,
        "dtype": "float32", **widths})
    params = nn.unbox(Transformer(cfg).init(
        jax.random.key(SEED), np.zeros((1, 8), np.int32))["params"])
    return cfg, params, DecodeConfig(max_new_tokens=NEW_TOKENS,
                                     temperature=0.0)


@pytest.fixture(scope="module", params=["dense", "looped"])
def lm(request):
    import jax

    cfg, params, decode = _stack(
        **(LOOPED if request.param == "looped" else {}))
    # Norm scales away from 1: a norm read with the wrong scale shows.
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a * (1.0 + 0.1 * np.sin(np.arange(a.size)).reshape(
            a.shape)) if path[-1].key == "scale" else a, params)
    return cfg, params, decode


def _programs(lm):
    """{name: (jitted function, arguments)} of the engine's programs at
    the tiny shapes."""
    from kubeflow_tpu.models import generate as g

    cfg, params, decode = lm
    state = g.init_paged_state(cfg, 4, 16, 8)
    tables = np.zeros((4, 4), np.int32)
    chunk = np.zeros((1, 8), np.int32)
    i32 = np.int32
    return {
        "decode_rounds": (g.decode_rounds, (
            cfg, params, state, decode, 4, tables, i32(4))),
        "prefill_chunk_into_slot": (g.prefill_chunk_into_slot, (
            cfg, params, state, decode, chunk, i32(0), i32(1), i32(1),
            i32(0), i32(0), tables[:1])),
    }


PROGRAMS = ["decode_rounds", "prefill_chunk_into_slot"]


def _scopes(lm):
    """A looped stack's norm between steps and its branch-output norms
    lie under a scope of their own."""
    return SCOPES | ({"kft.loop_norm"} if lm[0].loop_steps > 1 else set())


@pytest.mark.parametrize("program", PROGRAMS)
def test_scope_names_are_in_the_lowered_text(lm, program):
    fn, args = _programs(lm)[program]
    text = fn.lower(*args).as_text(debug_info=True)
    assert set(re.findall(r"kft\.[a-z_]+", text)) == _scopes(lm)


def test_kernel_step_program_keeps_its_scopes(
        lm, interpreted_paged_kernel):
    """With the paged attention kernel chosen ``decode_rounds`` gathers
    no view (no ``kft.kv_view``) and the kernel's own operations lie
    under ``kft.attention``, which ``programs.decode_attention_share``
    reads."""
    import jax

    fn, args = _programs(lm)["decode_rounds"]
    text = fn.lower(*args, paged_kernel=True).as_text(debug_info=True)
    assert set(re.findall(r"kft\.[a-z_]+", text)) \
        == _scopes(lm) - {"kft.kv_view"}
    jaxpr = jax.make_jaxpr(
        lambda *a: fn(*a, paged_kernel=True),
        static_argnums=(0, 3, 4))(*args)
    names = [e.primitive.name for e in _all_eqns(jaxpr.jaxpr)]
    assert "pallas_call" in names
    assert _unowned(jaxpr.jaxpr) == []


def _unowned(jaxpr, stack=(), in_layers=False):
    """Dots, gathers and scatters of the layer scan's body whose name
    stack holds no ``kft.`` scope."""
    import jax

    out = []
    for eqn in jaxpr.eqns:
        here = stack + (str(eqn.source_info.name_stack),)
        name = eqn.primitive.name
        if in_layers and (name in ("dot_general", "gather")
                          or name.startswith("scatter")
                          or name == "dynamic_update_slice") \
                and "kft." not in "/".join(here):
            out.append((name, here))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _unowned(sub, here, in_layers or name == "scan")
    return out


@pytest.mark.parametrize("program", PROGRAMS)
def test_every_dot_gather_and_scatter_of_the_layer_body_has_a_scope(
        lm, program):
    import jax

    fn, args = _programs(lm)[program]
    static = {"decode_rounds": (0, 3, 4),
              "prefill_chunk_into_slot": (0, 3)}[program]
    jaxpr = jax.make_jaxpr(fn, static_argnums=static)(*args)
    counted = [e.primitive.name for e in _all_eqns(jaxpr.jaxpr)]
    assert "scan" in counted and "dot_general" in counted
    assert _unowned(jaxpr.jaxpr) == []


def _all_eqns(jaxpr):
    import jax

    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_eqns(sub)


class Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps every
    annotation of every thread with its enter and exit times."""

    events = []

    def __init__(self, name, **facts):
        self.event = {"name": name, "facts": dict(facts),
                      "thread": threading.get_ident()}

    def __enter__(self):
        self.event["t0"] = time.perf_counter()
        Recorder.events.append(self.event)
        return self

    def set_metadata(self, **facts):
        self.event["facts"].update(facts)

    def __exit__(self, *exc):
        self.event["t1"] = time.perf_counter()
        return False


def _engine(lm, **kw):
    from kubeflow_tpu.serving.engine import DecodeEngine

    cfg, params, decode = lm
    kw.setdefault("slots", 3)
    kw.setdefault("prefill_len", 32)
    kw.setdefault("prefill_chunk_tokens", 8)
    kw.setdefault("kv_block_tokens", 4)
    return DecodeEngine(cfg, params, decode, **kw)


def _serve(engine, prompts, new=NEW_TOKENS):
    outs = [None] * len(prompts)

    def client(i):
        outs[i] = engine.submit({"tokens": np.asarray(prompts[i], np.int32),
                                 "max_new_tokens": new})

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return outs


def _prompts(n, length=9, seed=SEED):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, VOCAB, size=(length,)).tolist() for _ in range(n)]


# The round caps: the engine's own (8: what a bare engine and every
# cell run), a narrower one and one step a dispatch.
PATHS = {"cap8": {}, "cap4": {"decode_rounds": 4},
         "cap1": {"decode_rounds": 1}}


def _iterations(top):
    """The top-level annotations of the loop thread, cut at every
    ``admit``: one list an iteration."""
    out = []
    for e in top:
        if e["name"] == PREFIX + "admit":
            out.append([])
        out[-1].append(e)
    return out


@pytest.mark.parametrize("path", sorted(PATHS))
def test_phases_tile_every_iteration_in_order(lm, path, monkeypatch):
    """An iteration is its dispatching half (``admit`` ... ``overlap``,
    in rank order, stamped with its own number) and then the reading
    halves (``round_wait`` / ``drain`` / ``account``) of the rounds it
    reads: none where it leaves its round unread, the round before where
    the loop is ahead (PR 49), its own too where no round follows; each
    reading half carries the number of the iteration that DISPATCHED the
    round.  The top-level phases tile the thread's wall time."""
    import jax

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    Recorder.events = []
    engine = _engine(lm, name=f"phases-{path}", **PATHS[path])
    try:
        _serve(engine, _prompts(4))
        assert engine.compiled_programs()["decode_rounds"] == 1
        stats = engine.stats()
    finally:
        engine.close()
    events = [e for e in Recorder.events if "t1" in e]
    assert {e["thread"] for e in events} == {engine._thread.ident}
    assert {e["name"] for e in events} <= {
        "kft.engine." + p for p in list(RANK) + list(NESTED)}
    # Enter order is time order.
    assert [e["t0"] for e in events] == sorted(e["t0"] for e in events)
    top, opened = [], []
    for e in events:
        name = e["name"][len(PREFIX):]
        while opened and opened[-1]["t1"] <= e["t0"]:
            opened.pop()
        if opened:  # inside the one that is still open
            assert NESTED[name] == opened[-1]["name"][len(PREFIX):]
            assert e["t1"] <= opened[-1]["t1"]
            assert e["facts"]["round"] == opened[-1]["facts"]["round"]
        else:
            top.append(e)
        opened.append(e)
    iterations = _iterations(top)
    numbers = [group[0]["facts"]["round"] for group in iterations]
    assert numbers == list(range(numbers[0], numbers[0] + len(numbers)))
    stepped, ahead, covered, span, dispatched = 0, 0, 0.0, 0.0, set()
    for number, group in zip(numbers, iterations):
        names = [e["name"][len(PREFIX):] for e in group]
        if number == numbers[-1]:  # closed and drained: the loop left
            assert names == ["admit"]
            continue
        assert names[0] == "admit" and names[-1] == "account"
        # The halves: a new one begins at every top-level round_wait.
        halves = [[]]
        for e in group:
            if e["name"] == PREFIX + "round_wait":
                halves.append([])
            halves[-1].append(e)
        assert len(halves) <= 3
        for half in halves:
            ranks = [RANK[e["name"][len(PREFIX):]] for e in half]
            assert ranks == sorted(ranks), (number, names)
        # The closing account is the iteration's own again.
        assert halves[-1][-1]["facts"]["round"] == number
        assert all(e["facts"]["round"] == number for e in halves[0])
        if "round_dispatch" in names:
            stepped += 1
            dispatched.add(number)
            facts = group[names.index("round_dispatch")]["facts"]
            assert facts["live"] >= 1 and facts["width"] >= 1
        read = [half[0]["facts"]["round"] for half in halves[1:]]
        for of, half in zip(read, halves[1:]):
            # A round is read once, in its own iteration or the next.
            assert of in dispatched and number - 1 <= of <= number
            dispatched.remove(of)
            assert all(e["facts"]["round"] == of for e in half[:-1])
            ahead += of < number
        assert read == sorted(read)
        # Tiling: the top-level phases never overlap, and over the run
        # (below) they leave no hole worth a name.
        for a, b in zip(group, group[1:]):
            assert a["t1"] <= b["t0"]
        covered += sum(e["t1"] - e["t0"] for e in group)
        span += group[-1]["t1"] - group[0]["t0"]
    assert not dispatched  # every round that was dispatched was read
    assert 0.9 * span <= covered <= span
    assert stepped >= 3
    # A round read in the iteration after its own was dispatched ahead
    # of: the engine counts the same.
    assert ahead == stats["rounds_ahead"] >= 2
    chunks = sum(e["facts"].get("chunks", 0) for e in events
                 if e["name"].endswith("prefill_dispatch"))
    assert chunks == engine.stats()["prefill_chunks"]


@pytest.mark.parametrize("stop", ["budget", "eos"])
def test_round_wait_states_the_steps_and_positions_the_device_ran(
        lm, stop, monkeypatch):
    """``steps`` and ``attended`` on ``round_wait`` are the device's own
    counts: over a run they add up to the tokens the rounds emitted and
    to the cache positions those tokens' steps read, also where an EOS
    stops a slot before its budget (which no dispatch can know)."""
    import dataclasses

    import jax

    cfg, params, decode = lm
    prompts = _prompts(3)
    if stop == "eos":
        engine = _engine(lm, decode_rounds=4, name="facts-probe")
        try:
            probe = _serve(engine, prompts)
        finally:
            engine.close()
        # A token that request 0 emits in the middle of its answer.
        decode = dataclasses.replace(
            decode, eos_token=int(probe[0]["tokens"][0][len(prompts[0]) + 4]))
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    Recorder.events = []
    engine = _engine((cfg, params, decode), decode_rounds=4,
                     name=f"facts-{stop}")
    try:
        outs = _serve(engine, prompts)
    finally:
        engine.close()
    emitted = [len(o["tokens"][0]) - len(p) for o, p in zip(outs, prompts)]
    if stop == "eos":
        assert min(emitted) < NEW_TOKENS
    waits = [e["facts"] for e in Recorder.events
             if e["name"] == "kft.engine.round_wait" and "steps" in e["facts"]]
    dispatches = {e["facts"]["round"]: e["facts"] for e in Recorder.events
                  if e["name"] == "kft.engine.round_dispatch"}
    assert all(0 <= w["steps"] <= dispatches[w["round"]]["width"]
               for w in waits)
    # The first token of a request is its prefill's; token j (from 1) of
    # an answer was made by a step that read the prompt and j tokens.
    assert sum(w["attended"] for w in waits) == sum(
        len(p) + j for p, n in zip(prompts, emitted) for j in range(1, n))


def _loop_sums(stats):
    """The phase sums; ``loop_cpu_s`` is the thread's CPU clock."""
    return {k: v for k, v in stats.items() if k != "loop_cpu_s"
            and k.startswith("loop_") and k.endswith("_s")}


def _unblocked(stats):
    """Wall seconds of the phases in which the loop thread waits for
    nothing: all but ``wait_work`` and ``round_wait``."""
    return sum(v for k, v in _loop_sums(stats).items()
               if k not in ("loop_wait_work_s", "loop_round_wait_s"))


def test_loop_sums_are_monotone_and_add_up_to_the_wall_time(lm):
    engine = _engine(lm, decode_rounds=4, name="phases-sums")
    try:
        _serve(engine, _prompts(2))  # compiles; the loop goes idle
        time.sleep(0.05)
        a, t_a = engine.stats(), time.perf_counter()
        readings = [a]
        for batch in range(3):
            _serve(engine, _prompts(5, seed=batch))
            readings.append(engine.stats())
        time.sleep(0.05)  # idle again: the last wait_work is open
        b, t_b = engine.stats(), time.perf_counter()
        readings.append(b)
    finally:
        engine.close()
    assert len(_loop_sums(a)) == 11
    for before, after in zip(readings, readings[1:]):
        for key, value in _loop_sums(before).items():
            assert after[key] >= value
        for key in ("loop_rounds", "rounds_ahead", "loop_cpu_s",
                    "turnarounds", "turnaround_s_sum", "slow_rounds",
                    "slow_round_s_sum"):
            assert after[key] >= before[key]
    assert b["loop_rounds"] > a["loop_rounds"]
    grown = sum(_loop_sums(b).values()) - sum(_loop_sums(a).values())
    # The idle stretch before ``a`` is added when its wait ends (inside
    # the window) and the one before ``b`` is still open: 50 ms each way.
    assert grown == pytest.approx(t_b - t_a, rel=0.10, abs=0.06)
    assert b["loop_round_wait_s"] > a["loop_round_wait_s"]
    assert b["loop_round_read_s"] > a["loop_round_read_s"]
    # The thread's CPU seconds: it cannot have run for longer than the
    # phases in which it is not blocked lasted (10 ms for the reads of
    # two clocks an iteration apart and what a wake-up costs).
    assert 0 < b["loop_cpu_s"] - a["loop_cpu_s"] \
        <= _unblocked(b) - _unblocked(a) + 0.01
    # Most rounds were dispatched while the one before was unread, and
    # those open no turnaround (its own test holds what one counts).
    ahead = b["rounds_ahead"] - a["rounds_ahead"]
    assert 0 < ahead < b["fused_rounds"] - a["fused_rounds"]
    assert b["turnarounds"] - a["turnarounds"] \
        <= b["fused_rounds"] - a["fused_rounds"] - ahead


def _loop_events(engine):
    """The finished annotations of the engine's loop thread, bare phase
    names, in the order they were entered."""
    return [dict(e, name=e["name"][len(PREFIX):]) for e in Recorder.events
            if "t1" in e and e["thread"] == engine._thread.ident]


@pytest.mark.parametrize("path", sorted(PATHS))
def test_round_read_lies_inside_round_wait_in_every_round(
        lm, path, monkeypatch):
    """Every ``round_wait`` (a decode round's, the
    blocking read of a prefill's first token inside ``drain``) holds ONE
    ``round_read``, entered after the wait's own stretch and ending with
    it; the round's facts stay on ``round_wait``, which a traced run's
    readers match to the device's calls."""
    import jax

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    Recorder.events = []
    engine = _engine(lm, name=f"read-{path}", **PATHS[path])
    try:
        prompts = _prompts(4)
        _serve(engine, prompts)
        stats = engine.stats()
    finally:
        engine.close()
    events = _loop_events(engine)
    waits = [e for e in events if e["name"] == "round_wait"]
    reads = [e for e in events if e["name"] == "round_read"]
    drains = [e for e in events if e["name"] == "drain"]
    assert len(waits) == len(reads) >= 6
    for wait, read in zip(waits, reads):
        assert wait["facts"]["round"] == read["facts"]["round"]
        assert wait["t0"] <= read["t0"] <= read["t1"] <= wait["t1"]
        assert set(read["facts"]) == {"round"}
    in_drain = [w for w in waits if any(
        d["t0"] <= w["t0"] and w["t1"] <= d["t1"] for d in drains)]
    # One blocking read a prompt (its first token), the rest are rounds.
    assert len(in_drain) == len(prompts)
    rounds = [w for w in waits if not any(w is d for d in in_drain)]
    assert len(rounds) == stats["fused_rounds"]
    assert all("steps" in w["facts"] and "attended" in w["facts"]
               for w in rounds)
    # What the reads took is in stats() under its own name, and the
    # wait's own stretch no longer holds it.
    read_s = sum(e["t1"] - e["t0"] for e in reads)
    wait_s = sum(e["t1"] - e["t0"] for e in waits)
    assert 0 < stats["loop_round_read_s"] <= read_s
    assert stats["loop_round_wait_s"] <= wait_s - stats["loop_round_read_s"]


def _hands_work(event):
    """Does this annotation hold a call that hands the device work?"""
    return event["name"] == "round_dispatch" or (
        event["name"] == "prefill_dispatch"
        and event["facts"].get("chunks", 0) > 0)


def test_turnaround_is_first_result_to_the_next_dispatchs_return(
        lm, monkeypatch):
    """``turnaround_s_sum`` / ``turnarounds`` against the recorded
    annotations: a counted stretch starts where a ``round_read`` was
    entered with NOTHING queued on the device (the call it read was the
    last that handed the device work) and ends inside the next
    annotation that hands the device work, a chunk's where that comes
    before the round's; the dispatching annotation states it
    (``since_ready_us``).  A round the loop got ahead of (the next was
    dispatched before it was read, PR 49) opens none, and nothing is
    counted across a ``wait_work`` in which the loop had nothing to
    do."""
    import jax

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    Recorder.events = []
    engine = _engine(lm, decode_rounds=4, name="turnaround")
    try:
        _serve(engine, _prompts(2))
        idle_from = time.perf_counter()
        time.sleep(0.08)  # the loop finds nothing to do and waits
        idle_to = time.perf_counter()
        # Long prompts with one decode step each: a request's only round
        # retires it at dispatch while the next prompt is still being
        # prefilled, so no round follows, the round is read at once and
        # the next CHUNK ends the turnaround.
        _serve(engine, _prompts(5, length=29, seed=3), new=2)
        stats = engine.stats()
    finally:
        engine.close()
    events = _loop_events(engine)
    # The calls that hand the device work, numbered as the engine does;
    # a chunk's first token is taken for the last chunk's of its
    # annotation.
    handed, of_round, of_chunk = 0, {}, 0
    ready, counted, by_chunk, stated, ahead = None, [], 0, {}, 0
    drains = [e for e in events if e["name"] == "drain"]
    for e in events:
        if e["name"] == "round_read":
            in_drain = any(d["t0"] <= e["t0"] and e["t1"] <= d["t1"]
                           and d["t0"] < e["t0"] for d in drains)
            was = of_chunk if in_drain else of_round[e["facts"]["round"]]
            if was == handed:
                assert ready is None
                ready = e["t0"]
            elif not in_drain:
                ahead += 1
        if not _hands_work(e):
            continue
        if e["name"] == "round_dispatch":
            handed += 1
            of_round[e["facts"]["round"]] = handed
        else:
            handed += e["facts"]["chunks"]
            of_chunk = handed
        if "since_ready_us" in e["facts"]:
            took = e["facts"]["since_ready_us"] / 1e6
            # The stretch ends at the call's return, inside e.
            assert ready is not None
            assert e["t0"] - ready - 1e-4 <= took <= e["t1"] - ready
            counted.append((ready, took))
            by_chunk += e["name"] == "prefill_dispatch"
            stated.setdefault(e["facts"]["round"], []).append(e["name"])
        ready = None
    assert len(counted) == stats["turnarounds"]
    assert stats["turnaround_s_sum"] == pytest.approx(
        sum(took for _, took in counted), abs=2e-6 * len(counted))
    # The rounds the loop got ahead of opened none: each is the round
    # BEFORE one that ``rounds_ahead`` counts.
    assert ahead == stats["rounds_ahead"] >= 2
    assert stats["turnarounds"] + ahead <= stats["fused_rounds"]
    # The chunk's call ended the turnaround where it came first, and
    # that iteration's round found none open.
    assert by_chunk >= 2
    assert all(len(names) == 1 for names in stated.values())
    # The idle stretch: no counted turnaround spans its middle, and the
    # first hand-over after it closes none.
    middle = (idle_from + idle_to) / 2
    assert not any(start < middle < start + took
                   for start, took in counted)
    first = next(e for e in events if _hands_work(e) and e["t0"] > idle_to)
    assert "since_ready_us" not in first["facts"]
    last = max(e["t1"] for e in events if e["t1"] < middle)
    assert any(e["name"] == "round_read" and e["t0"] <= last
               for e in events)  # a turnaround WAS open when it went idle


def test_a_slow_iteration_is_counted_and_logged_once(
        lm, monkeypatch, caplog):
    """A ``faults`` sleep at ``engine.step`` (in ``round_prepare``) makes
    one iteration many times the running mean: ``slow_rounds`` + 1, its
    excess in ``slow_round_s_sum``, ONE warning that names the phase and
    carries the iteration's eleven own phase times, and none for the
    rounds beside it."""
    import logging

    from kubeflow_tpu.serving import engine as engine_module
    from kubeflow_tpu.testing import faults

    assert engine_module._SLOW_ROUND_FACTOR == 8
    # A test box under load hiccups by 8 times a 2 ms iteration; the
    # sleep below is 100 times one.
    monkeypatch.setattr(engine_module, "_SLOW_ROUND_FACTOR", 40)
    engine = _engine(lm, decode_rounds=4, name="slow")
    try:
        for batch in range(3):  # compiles, then a mean to hold against
            _serve(engine, _prompts(3, seed=batch))
        before = engine.stats()
        with caplog.at_level(logging.WARNING,
                             logger="kubeflow_tpu.serving.engine"):
            with faults.injected("seed=1;engine.step:sleep=0.4*1"):
                _serve(engine, _prompts(3, seed=7))
            _serve(engine, _prompts(3, seed=8))
        after = engine.stats()
    finally:
        engine.close()
    assert before["slow_rounds"] == 0 and before["slow_round_s_sum"] == 0
    assert after["slow_rounds"] == 1
    assert 0.35 < after["slow_round_s_sum"] < 0.6
    logged = [r.getMessage() for r in caplog.records
              if "slow iteration" in r.getMessage()]
    assert len(logged) == 1
    text = logged[0]
    assert "engine 'slow'" in text and "most of it in round_prepare" in text
    times = dict(re.findall(r"(\w+)=(\d+\.\d+)", text))
    assert set(times) == {
        "wait_work", "admit", "housekeeping", "prefill_dispatch",
        "round_prepare", "round_dispatch", "overlap", "round_wait",
        "round_read", "drain", "account"}
    assert float(times["round_prepare"]) >= 0.4
    assert max(float(v) for k, v in times.items()
               if k != "round_prepare") < 0.1
    facts = dict(re.findall(r"(width|steps|live|admitted|chunks)=(\S+)",
                            text))
    assert set(facts) == {"width", "steps", "live", "admitted", "chunks"}
    assert int(facts["live"]) >= 1 and 1 <= int(facts["width"]) <= 4


@pytest.mark.parametrize("prefix_hit", [False, True])
def test_queue_wait_and_prefill_span_equal_the_traced_spans(
        lm, prefix_hit, monkeypatch):
    from kubeflow_tpu.runtime import tracing

    spans = []
    tracing.enable(sample_rate=1.0)
    monkeypatch.setattr(
        tracing, "record_span",
        lambda name, ctx, start, end, **kw: spans.append(
            (name, ctx.trace_id, start, end)))
    engine = _engine(lm, decode_rounds=4, slots=2,
                     name=f"phases-spans-{int(prefix_hit)}")
    try:
        shared = _prompts(1, length=16)[0]
        prompts = [shared + tail for tail in _prompts(5, length=3)] \
            if prefix_hit else _prompts(5, length=19)
        before = engine.stats()
        if prefix_hit:  # make the shared pages resident first
            _serve(engine, [shared + [1, 2, 3]])

        def client(prompt):
            root = tracing.start_span("client")
            with tracing.use_span(root):
                engine.submit({"tokens": np.asarray(prompt, np.int32),
                               "max_new_tokens": 6})
            root.end()

        warm = engine.stats()
        threads = [threading.Thread(target=client, args=(p,))
                   for p in prompts]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        after = engine.stats()
    finally:
        engine.close()
        tracing.disable()
    sent = len(prompts)
    assert after["admitted"] - warm["admitted"] == sent
    assert after["first_tokens"] - warm["first_tokens"] == sent
    assert after["first_tokens_hit"] - warm["first_tokens_hit"] == (
        sent if prefix_hit else 0)
    assert before["admitted"] == before["first_tokens"] == 0
    admission = {t: (s, e) for n, t, s, e in spans
                 if n == "engine.admission"}
    decode = {t: (s, e) for n, t, s, e in spans if n == "engine.decode"}
    assert len(admission) == len(decode) == sent
    waited = sum(e - s for s, e in admission.values())
    prefilled = sum(decode[t][0] - admission[t][1] for t in admission)
    assert after["queue_wait_s_sum"] - warm["queue_wait_s_sum"] == \
        pytest.approx(waited, abs=1e-9)
    assert after["prefill_span_s_sum"] - warm["prefill_span_s_sum"] == \
        pytest.approx(prefilled, abs=1e-9)
    hit = after["prefill_span_hit_s_sum"] - warm["prefill_span_hit_s_sum"]
    assert hit == pytest.approx(prefilled if prefix_hit else 0.0, abs=1e-9)
    # With two slots for five requests some of them queued.
    assert waited > 0 and prefilled > 0


def test_compile_counters_are_set_once(lm):
    engine = _engine(lm, decode_rounds=4, name="phases-compile")
    try:
        assert engine.stats()["compile_s"] == 0.0
        assert engine.stats()["compiled_peak_bytes"] == 0
        _serve(engine, _prompts(1))
        first = engine.stats()
        _serve(engine, _prompts(3, seed=1))
        second = engine.stats()
    finally:
        engine.close()
    assert first["compile_s"] > 0.0
    assert first["compiled_peak_bytes"] > 0
    assert second["compile_s"] == first["compile_s"]
    assert second["compiled_peak_bytes"] == first["compiled_peak_bytes"]
    assert second["compiled_programs"] == first["compiled_programs"] == {
        "chunked_prefill": 1, "decode_rounds": 1}


def test_pool_hand_off_and_stats_are_sized_by_planes(lm):
    """What the engine sizes by the pool's leading axis: ``stats()``, the
    pages a prefill replica exports (``gather_kv_pages``), the shape a
    decode replica accepts and scatters (``import_kv_pages``), and pages
    shared through the prefix cache.  A looped stack has more planes than
    layers; tokens after a hand-off and after a cache hit are the local
    run's."""
    cfg, _, _ = lm
    prompt = _prompts(1, length=19)[0]
    pre = _engine(lm, decode_rounds=4, name="phases-planes-pre")
    dec = _engine(lm, decode_rounds=4, prefix_caching=False,
                  name="phases-planes-dec")
    try:
        stats = pre.stats()
        assert stats["loop_steps"] == cfg.loop_steps
        assert stats["kv_planes"] == cfg.loop_steps * cfg.n_layers
        # keys + values, float32, 2 kv heads of 8: 128 B a plane.
        assert stats["kv_bytes_per_token"] == 128 * stats["kv_planes"]
        assert pre._state["cache_k"].shape[0] == stats["kv_planes"]
        local = _serve(pre, [prompt])[0]["tokens"][0].tolist()
        again = _serve(pre, [prompt])[0]["tokens"][0].tolist()
        assert again == local and pre.stats()["prefix_hits"] == 1
        hand = pre.prefill_export({"tokens": prompt})["kv_handoff"]
        assert hand["k"].shape == (stats["kv_planes"], 4, 4, 2, 8)
        got = dec.submit({"tokens": np.asarray(prompt, np.int32),
                          "max_new_tokens": NEW_TOKENS,
                          "kv_handoff": hand})
        assert got["tokens"][0].tolist() == local
        assert dec.stats()["handoff_pages_in"] == 4
        with pytest.raises(ValueError, match="planes="):
            dec.submit({"tokens": np.asarray(prompt, np.int32),
                        "kv_handoff": dict(hand, k=hand["k"][:-1],
                                           v=hand["v"][:-1])})
    finally:
        pre.close()
        dec.close()


# -- the chunk's width (PR 36) -------------------------------------------------

@pytest.mark.parametrize("prefill_len", [16, 40, 300])
def test_an_engine_built_without_a_width_takes_the_constants(lm, prefill_len):
    """One constant in the tree: the engine, ``batcher_factory`` and the
    serving binary's flag default to it, and ``prefill_len`` clamps it."""
    import dataclasses
    import inspect

    from kubeflow_tpu.serving import engine as engine_module
    from kubeflow_tpu.serving import main as serving_main

    assert engine_module.PREFILL_CHUNK_TOKENS == 256
    for fn in (engine_module.DecodeEngine.__init__,
               serving_main.batcher_factory):
        assert inspect.signature(fn).parameters[
            "prefill_chunk_tokens"].default == 256
    cfg, params, decode = lm
    engine = engine_module.DecodeEngine(
        dataclasses.replace(cfg, max_seq_len=512), params, decode,
        slots=2, prefill_len=prefill_len, name=f"width-{prefill_len}")
    try:
        assert engine.chunk_w == min(256, prefill_len)
    finally:
        engine.close()


@pytest.mark.parametrize("width", [None, 8])
def test_one_chunk_of_the_oldest_admission_between_two_rounds(
        lm, width, monkeypatch):
    """The ``chunks`` fact of ``prefill_dispatch``: a claim dispatches the
    admission's first chunk, and beyond the claims an iteration runs at
    most ONE chunk, of the oldest admission, before its decode round.
    Also where nobody states a width and ``prefill_len`` clamps the
    default: every prompt is then one chunk, dispatched at its claim, and
    the loop adds none (``_prefilling`` stays empty)."""
    import jax

    from kubeflow_tpu.serving.engine import DecodeEngine

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    Recorder.events = []
    kw = {"prefill_chunk_tokens": width} if width else {}
    cfg, params, decode = lm
    engine = DecodeEngine(cfg, params, decode, slots=3, prefill_len=32,
                          kv_block_tokens=4, decode_rounds=4,
                          name=f"one-chunk-{width}", **kw)
    try:
        assert engine.chunk_w == (width or 32)
        prompts = _prompts(6, length=29)
        _serve(engine, prompts)
        stats = engine.stats()
    finally:
        engine.close()
    per_prompt = -(-29 // engine.chunk_w)
    assert stats["prefill_chunks"] == 6 * per_prompt
    facts = [e["facts"] for e in Recorder.events
             if e["name"] == "kft.engine.prefill_dispatch" and "t1" in e]
    assert sum(f["chunks"] for f in facts) == stats["prefill_chunks"]
    assert sum(f["admitted"] for f in facts) == 6
    for f in facts:
        assert f["admitted"] <= f["chunks"] <= f["admitted"] + 1
    if width is None:
        assert all(f["chunks"] == f["admitted"] for f in facts)
    else:
        # Mid-prefill prompts do get their chunk where nothing is claimed.
        assert any(f["chunks"] == 1 and f["admitted"] == 0 for f in facts)


@pytest.mark.parametrize("beside", ["a_live_slot", "nothing"])
def test_a_wide_chunk_waits_for_the_rounds_to_save_it_up(beside,
                                                         monkeypatch):
    """A chunk wider than ``PREFILL_ROUND_TOKENS``: while a slot is live
    a round saves up 64 of its 128 columns, so a prompt in mid-prefill
    gets its next chunk every SECOND round; with nothing live it gets
    one every iteration."""
    import jax

    from kubeflow_tpu.serving import engine as engine_module

    assert engine_module.PREFILL_ROUND_TOKENS == 64
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    Recorder.events = []
    cfg, params, decode = _stack(max_seq_len=512)
    # The live slot's 60 tokens are the export's budget too, so that it
    # outlives the long prompt's chunks however the box is loaded.
    decode = dataclasses.replace(decode, max_new_tokens=60)
    engine = _engine((cfg, params, decode), slots=2, prefill_len=448,
                     prefill_chunk_tokens=128, decode_rounds=1,
                     name=f"saved-up-{beside}")
    long_prompt = _prompts(1, length=440, seed=7)[0]
    try:
        if beside == "a_live_slot":
            live = threading.Thread(target=_serve, args=(
                engine, _prompts(1, length=5), 60))
            live.start()
            while engine.stats()["first_tokens"] < 1:
                time.sleep(0.001)
        _serve(engine, [long_prompt], new=2)
        if beside == "a_live_slot":
            live.join()
    finally:
        engine.close()
    dispatched = {e["facts"]["round"] for e in Recorder.events
                  if e["name"] == "kft.engine.round_dispatch"}
    chunks = sorted(
        e["facts"]["round"] for e in Recorder.events
        if e["name"] == "kft.engine.prefill_dispatch" and "t1" in e
        and e["facts"]["chunks"] == 1 and e["facts"]["admitted"] == 0)
    apart = {b - a for a, b in zip(chunks, chunks[1:])}
    # 440 columns are four chunks, the first at the claim.
    if beside == "a_live_slot":
        assert len(chunks) == 3 and apart == {2}
        assert set(range(chunks[0], chunks[-1])) <= dispatched
    else:  # the claim's iteration runs the second chunk too
        assert len(chunks) == 2 and apart == {1}
        assert not dispatched & set(chunks[:-1])


def _conv_stack():
    """A stack with ``layer_types``: convolution layers with a per-slot
    state beside the pool, a dense feed-forward and sparse experts."""
    return _stack(
        n_layers=3, layer_types=["conv", "full_attention", "conv"],
        conv_kernel=3, qk_norm=True, tied_embeddings=True, moe_experts=4,
        moe_top_k=2, moe_dense_layers=1, moe_d_ff=24)


_LONG, _TAIL = _prompts(1, length=22)[0], _prompts(1, length=9, seed=3)[0]
# name -> (stack, chunk widths, prompts served one after the other, prompt
# tokens the LAST of them finds cached).  Pages are 4 positions: the
# second prompt shares 14 tokens with the first and resumes at 12, inside
# a chunk of 8 or 16 columns.  At 128 and 192 columns the view's attention
# runs in 2 and 3 tiles of query rows (``generate._VIEW_QUERY_TILE``), at
# 64 in one call.
WIDTH_CASES = {
    "cold": ("dense", (4, 8, 16), [_LONG], 0),
    "prefix_hit_mid_page": (
        "dense", (4, 8, 16), [_LONG, _LONG[:14] + _TAIL], 12),
    "shorter_than_a_chunk": ("dense", (4, 8, 16), [_LONG[:3]], 0),
    "conv_state": ("conv", (4, 8, 16), [_LONG, _LONG[:3]], 0),
    "query_tiles": ("long", (64, 128, 192),
                    [_prompts(1, length=150, seed=5)[0], _LONG], 0),
}


@pytest.mark.parametrize("case", sorted(WIDTH_CASES))
def test_the_chunks_width_changes_no_token(case):
    """The same prompts at three chunk widths: the same answers, in the
    chunks each width makes of what the cache did not hold."""
    stack, widths, prompts, cached = WIDTH_CASES[case]
    model = {"dense": _stack, "conv": _conv_stack,
             "long": lambda: _stack(max_seq_len=256)}[stack]()
    prefill_len = 192 if stack == "long" else 32
    fresh = [len(p) for p in prompts]
    fresh[-1] -= cached
    answers = []
    for width in widths:
        engine = _engine(model, prefill_chunk_tokens=width, decode_rounds=4,
                         prefill_len=prefill_len,
                         name=f"width-{case}-{width}")
        try:
            assert engine.chunk_w == width
            outs = [_serve(engine, [p])[0] for p in prompts]
            stats = engine.stats()
        finally:
            engine.close()
        assert stats["cached_prompt_tokens"] == cached
        assert stats["prompt_tokens"] - cached == sum(fresh)
        assert stats["prefill_chunks"] == sum(-(-n // width) for n in fresh)
        answers.append([o["tokens"][0].tolist() for o in outs])
        assert [len(a) for a in answers[-1]] == [
            len(p) + NEW_TOKENS for p in prompts]
    assert answers[0] == answers[1] == answers[2]


@pytest.mark.parametrize("rows", [128, 192])
@pytest.mark.parametrize("per_row", [False, True])
def test_view_attention_in_query_tiles_is_the_whole_calls(rows, per_row):
    """``_view_attention`` over several tiles of query rows against ONE
    ``dot_product_attention`` over all of them: grouped heads, a view
    longer than the rows, a scalar frontier and one per row."""
    import jax.numpy as jnp

    from kubeflow_tpu.models import generate
    from kubeflow_tpu.ops.attention import dot_product_attention

    assert rows % generate._VIEW_QUERY_TILE == 0
    rng = np.random.default_rng(SEED)
    q = jnp.asarray(rng.normal(size=(2, rows, 4, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, 256, 2, 8)), jnp.float32)
            for _ in range(2))
    start = jnp.asarray([40, 7], jnp.int32) if per_row else jnp.int32(40)
    got = generate._view_attention(q, k, v, start, None)
    want = dot_product_attention(q, k, v, causal=True, kv_offset=start)
    assert got.shape == want.shape == q.shape
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)


def _paged(rng, view, hkv, d, planes=2, spare=7):
    """A stacked pool side and ONE row's table over it, pages in a
    shuffled order: ``(pool [planes, nb, 16, hkv, d], tables [1, view /
    16])``."""
    import jax.numpy as jnp

    pages = view // 16
    pool = jnp.asarray(
        rng.normal(size=(planes, pages + spare, 16, hkv, d)), jnp.float32)
    order = rng.permutation(pages + spare)[:pages]
    return pool, jnp.asarray(order[None], jnp.int32)


# A table of 1,280 positions in pages of 16 under a call of 128 columns:
# key tiles of 160 positions (an eighth), 8 of them.  ``start`` so that
# the call's last position (start + 127) lies in the first tile, on both
# sides of a tile's edge, and in the table's last chunk; and so that the
# call itself starts on both sides of an edge.
HELD_STARTS = [0, 159 - 127, 160 - 127, 161 - 127, 159, 160, 161,
               2 * 160 - 128, 1280 - 128]


@pytest.mark.parametrize("int8", [False, True], ids=["array", "int8"])
@pytest.mark.parametrize("start", HELD_STARTS)
def test_view_attention_over_held_tiles_is_the_whole_views(start, int8):
    """The chunk program's attention over the key tiles the call can see
    (``_held_key_tiles`` + ``_tiled_view_attention``) against ONE
    ``dot_product_attention`` over the row's whole gathered view: grouped
    heads, an int8 ``QTensor`` pool, and pages past the visited tiles
    that hold NaN (never gathered, so the result stays finite; the one
    pass is given the clean pool)."""
    import jax.numpy as jnp

    from kubeflow_tpu.models import generate
    from kubeflow_tpu.ops.attention import dot_product_attention
    from kubeflow_tpu.ops.quantize import QTensor, quantize_array

    view, t, h, hkv, d, plane = 1280, 128, 4, 2, 8, 1
    assert generate.view_key_tiles(view // 16, 16, t) == (160, 8)
    rng = np.random.default_rng(SEED + start)
    q = jnp.asarray(rng.normal(size=(1, t, h, d)), jnp.float32)
    (pool_k, tables), (pool_v, _) = (
        _paged(rng, view, hkv, d), _paged(rng, view, hkv, d))
    scored = generate.view_positions_scored(view // 16, 16, t, start + t)
    assert start + t <= scored <= view and scored % 160 == 0
    assert scored - (start + t) < 160

    def spoiled(pool):
        unseen = tables[0, scored // 16:]
        return pool.at[:, unseen].set(jnp.nan)

    def side(pool):
        if not int8:
            return pool
        values, scale = quantize_array(pool, (-1,))
        return QTensor(values, scale, (-1,))

    def row_view(c, pages):
        def gather(p):
            return p[plane, pages].reshape((1, -1) + p.shape[3:])
        if int8:
            return QTensor(gather(c.values), gather(c.scale), c.axes)
        return gather(c)

    want = dot_product_attention(
        q, row_view(side(pool_k), tables), row_view(side(pool_v), tables),
        causal=True, kv_offset=jnp.int32(start))
    tile, visited, pages_of = generate._held_key_tiles(
        tables, 16, t, jnp.int32(start))
    assert (tile, int(visited)) == (160, scored // 160)

    ck, cv = side(pool_k), side(pool_v)
    if not int8:
        ck, cv = spoiled(ck), spoiled(cv)
    got = generate._tiled_view_attention(
        q, lambda i: (row_view(ck, pages_of(i)), row_view(cv, pages_of(i))),
        hkv, jnp.int32(start), None, tile, visited, view)
    assert got.shape == q.shape
    np.testing.assert_allclose(got, want, rtol=0, atol=5e-6)


@pytest.mark.parametrize("t, view, one_pass", [
    (1, 6624, True), (5, 6624, True), (64, 6624, True), (96, 6624, True),
    (128, 512, True), (256, 704, True), (256, 1024, True),
    (128, 1040, False), (256, 2560, False), (256, 6400, False),
    (256, 6624, False)])
def test_which_calls_visit_a_view_by_key_tiles(t, view, one_pass):
    """A decode step, a call of one query tile and a table of
    one or two key tiles (reason's 512, workers' 704 positions) run one
    pass; the cells' long tables are visited in tiles no coarser than an
    eighth of the table or 512 positions, in whole pages."""
    from kubeflow_tpu.models import generate

    tile, tiles = generate.view_key_tiles(view // 16, 16, t)
    if one_pass:
        assert (tile, tiles) == (view, 1)
        assert generate.view_positions_scored(view // 16, 16, t, 1) == view
        return
    assert tile % 16 == 0 and tile <= min(view // 8, 512)
    assert (tiles - 1) * tile < view <= tiles * tile
    held = [1, tile - 1, tile, tile + 1, view - 1, view, view + 200]
    scored = [generate.view_positions_scored(view // 16, 16, t, n)
              for n in held]
    assert scored == [tile, tile, tile, 2 * tile, view, view, view]


def test_left_padded_rows_over_held_tiles_mask_their_pad():
    """Rows with a ``pad_amount`` and a frontier of their own through the
    tile loop: the pad's keys get no weight, the rows' own bound is the
    furthest row's."""
    import jax.numpy as jnp

    from kubeflow_tpu.models import generate
    from kubeflow_tpu.ops.attention import dot_product_attention

    view, t, h, hkv, d = 1280, 128, 4, 2, 8
    rng = np.random.default_rng(SEED)
    q = jnp.asarray(rng.normal(size=(2, t, h, d)), jnp.float32)
    pool_k, row = _paged(rng, view, hkv, d, planes=1, spare=80)
    pool_v, _ = _paged(rng, view, hkv, d, planes=1, spare=80)
    tables = jnp.concatenate([row, (row + 3) % pool_k.shape[1]])
    start = jnp.asarray([300, 40], jnp.int32)
    pad = jnp.asarray([170, 3], jnp.int32)

    def row_view(p, pages):
        return p[0, pages].reshape((2, -1) + p.shape[3:])

    want = dot_product_attention(
        q, row_view(pool_k, tables), row_view(pool_v, tables), causal=True,
        kv_offset=start, kv_valid_start=pad)
    tile, visited, pages_of = generate._held_key_tiles(tables, 16, t, start)
    assert int(visited) == 3                 # 300 + 128 positions of 160
    got = generate._tiled_view_attention(
        q, lambda i: (row_view(pool_k, pages_of(i)),
                      row_view(pool_v, pages_of(i))), hkv, start, pad, tile,
        visited, view)
    np.testing.assert_allclose(got, want, rtol=0, atol=5e-6)


@pytest.mark.parametrize("kv_cache_dtype", ["model", "int8"])
@pytest.mark.parametrize("start", [0, 160 - 128, 161 - 128, 700,
                                   1280 - 128, 1280 - 64])
def test_a_chunk_over_a_long_table_is_the_one_pass_chunk(
        start, kv_cache_dtype, monkeypatch):
    """``_forward_with_cache`` of a 128-column chunk at ``start`` against
    a slot whose table holds 1,280 positions (the attention block takes
    the key-tile loop) and the same call made to pass over the whole
    view (a key tile as long as the table): the same logits and the same
    pool, grouped heads, a bfloat16-free float32 stack, a plain and an
    int8 pool; the last chunk overhangs the table."""
    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    from kubeflow_tpu.models import generate
    from kubeflow_tpu.models.transformer import (
        Transformer,
        TransformerConfig,
    )
    from kubeflow_tpu.ops.quantize import QTensor

    cfg = TransformerConfig(
        vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, head_dim=8, max_seq_len=2048, dtype=jnp.float32)
    params = nn.unbox(Transformer(cfg).init(
        jax.random.key(SEED), jnp.zeros((1, 8), jnp.int32))["params"])
    rng = np.random.default_rng(SEED + start)
    state = generate.init_paged_state(cfg, 2, 2 * 80, 16,
                                      kv_cache_dtype=kv_cache_dtype)

    def fill(c):
        if isinstance(c, QTensor):
            return QTensor(
                jnp.asarray(rng.integers(-120, 120, c.values.shape),
                            c.values.dtype),
                jnp.asarray(rng.uniform(0.004, 0.02, c.scale.shape),
                            c.scale.dtype), c.axes)
        return jnp.asarray(rng.normal(size=c.shape), c.dtype)

    cache = fill(state["cache_k"]), fill(state["cache_v"])
    tables = jnp.asarray(rng.permutation(160)[None, :80], jnp.int32)
    chunk = jnp.asarray(rng.integers(1, VOCAB, (1, 128)), jnp.int32)

    def forward():
        logits, (ck, _) = generate._forward_with_cache(
            cfg, params, chunk, cache, jnp.int32(start), tables=tables)
        return np.asarray(logits), np.asarray(
            ck.values if isinstance(ck, QTensor) else ck)

    assert generate.view_key_tiles(80, 16, 128) == (160, 8)
    tiled = forward()
    monkeypatch.setattr(generate, "_VIEW_KEY_TILE", 1280)
    assert generate.view_key_tiles(80, 16, 128) == (1280, 1)
    whole = forward()
    assert np.ptp(whole[0]) > 0.3
    assert np.abs(tiled[0] - whole[0]).max() < 2e-4
    if kv_cache_dtype == "model":
        assert np.abs(tiled[1] - whole[1]).max() < 2e-4
