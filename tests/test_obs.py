"""Observability layer: span tracer (exact timings via injected clocks,
ring bounds, Chrome-trace schema), mergeable metrics registry (snapshot
isolation, associative merge), the scheduler's schema-driven telemetry
contract, and the zero-new-device-syncs guarantee of tracing the serving
hot path."""
import json
import time

import jax
import numpy as np
import pytest

from _hyp import given, settings, st
from repro.obs.clock import ManualClock
from repro.obs.metrics import (DEFAULT_BOUNDS, MetricsRegistry,
                               merge_snapshots)
from repro.obs.trace import (NULL_TRACER, SpanTracer, validate_chrome_trace)
from repro.launch.obs_report import summarize
from repro.launch.obs_report import main as obs_report_main
from repro.models.transformer import init_params
from repro.serve.scheduler import TELEMETRY_SCHEMA, ServeScheduler
from repro.train.optimizer import OptimizerConfig
from repro.train.trainer import Trainer, init_train_state

from test_serve import _cfg, _request_material


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def test_tracer_manual_clock_exact_timings():
    """Injected clock -> exact ts/dur in microseconds, no tolerances."""
    clk = ManualClock()
    tr = SpanTracer(clock=clk)
    with tr.span("outer"):
        clk.advance(1.0)
        with tr.span("inner", row=3) as sp:
            clk.advance(0.5)
            sp.set(bucket=16)
        clk.advance(0.25)
    inner, outer = tr.events()               # inner exits first
    assert inner["name"] == "inner" and outer["name"] == "outer"
    assert inner["ts"] == pytest.approx(1.0e6)
    assert inner["dur"] == pytest.approx(0.5e6)
    assert inner["args"] == {"row": 3, "bucket": 16}
    assert outer["ts"] == pytest.approx(0.0)
    assert outer["dur"] == pytest.approx(1.75e6)
    # positional nesting: inner's range sits inside outer's on one tid
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert inner["tid"] == outer["tid"]


def test_tracer_instant_counter_and_clear():
    clk = ManualClock()
    tr = SpanTracer(clock=clk)
    clk.advance(2.0)
    tr.instant("admission", rid=1)
    tr.counter("queue_depth", 4)
    ev_i, ev_c = tr.events()
    assert ev_i["ph"] == "i" and ev_i["s"] == "t"
    assert ev_i["ts"] == pytest.approx(2.0e6)
    assert ev_c["ph"] == "C" and ev_c["args"] == {"value": 4}
    # clear re-anchors the epoch: new events start at ts 0 again
    tr.clear()
    assert len(tr) == 0
    tr.instant("after")
    assert tr.events()[0]["ts"] == pytest.approx(0.0)


def test_tracer_ring_bounds_and_drop_count():
    tr = SpanTracer(clock=ManualClock(), capacity=4)
    for i in range(10):
        tr.instant(f"e{i}")
    assert len(tr) == 4
    assert tr.dropped == 6
    assert [e["name"] for e in tr.events()] == ["e6", "e7", "e8", "e9"]
    assert tr.to_chrome_trace()["otherData"]["dropped_events"] == 6


def test_null_tracer_is_inert():
    assert NULL_TRACER.enabled is False
    assert len(NULL_TRACER) == 0
    sp = NULL_TRACER.span("x", a=1)
    assert NULL_TRACER.span("y") is sp       # shared instance, no alloc
    with sp:
        sp.set(b=2)
    NULL_TRACER.instant("i")
    NULL_TRACER.counter("c", 1)
    NULL_TRACER.clear()
    assert len(NULL_TRACER) == 0


def test_validate_chrome_trace_accepts_tracer_output(tmp_path):
    clk = ManualClock()
    tr = SpanTracer(clock=clk)
    with tr.span("step"):
        clk.advance(0.1)
    tr.instant("finish", rid=0)
    tr.counter("queue_depth", 0)
    doc = tr.to_chrome_trace()
    assert validate_chrome_trace(doc) == []
    # and the round-trip through save() stays valid JSON + schema
    path = tmp_path / "t.json"
    tr.save(str(path))
    assert validate_chrome_trace(json.loads(path.read_text())) == []


def test_validate_chrome_trace_rejects_malformed():
    assert validate_chrome_trace([]) != []               # root not object
    assert validate_chrome_trace({}) != []               # no traceEvents
    assert validate_chrome_trace({"traceEvents": {}}) != []
    good = {"name": "x", "ph": "i", "ts": 0.0, "pid": 1, "tid": 1}
    for mutation, frag in (
            (dict(good, ph="Z"), "bad ph"),
            (dict(good, ts=-1.0), "bad ts"),
            (dict(good, name=""), "name"),
            (dict(good, pid="1"), "pid"),
            ({"name": "x", "ph": "X", "ts": 0.0, "pid": 1, "tid": 1},
             "dur"),                                     # X without dur
            (dict(good, ph="C"), "args"),                # C without args
            (dict(good, args=[1]), "args"),
    ):
        problems = validate_chrome_trace({"traceEvents": [mutation]})
        assert any(frag in p for p in problems), (mutation, problems)
    # metadata-only trace is "valid but empty" -> flagged by default,
    # accepted when emptiness is expected
    meta_only = {"traceEvents": [
        {"name": "process_name", "ph": "M", "ts": 0, "pid": 1, "tid": 0}]}
    assert validate_chrome_trace(meta_only) != []
    assert validate_chrome_trace(meta_only, require_nonempty=False) == []


def test_span_overhead_bounded():
    """Tracing must stay a clock read + append: the per-span cost bound
    here is what makes --trace safe on the serving hot path."""
    tr = SpanTracer()
    n = 2000
    t0 = time.perf_counter()
    for i in range(n):
        with tr.span("step", i=i):
            pass
    per_span = (time.perf_counter() - t0) / n
    assert len(tr) == n
    assert per_span < 200e-6, f"span overhead {per_span*1e6:.1f}us/span"


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_basics():
    reg = MetricsRegistry()
    c = reg.counter("serve.steps")
    c.inc()
    c.inc(4)
    assert reg.counter("serve.steps") is c and c.value == 5
    g = reg.gauge("jit.compile_s")
    g.set(1.5)
    g.set(2.5)
    assert g.value == 2.5 and g.seq == 2
    h = reg.histogram("serve.queue_depth")
    for v in (0, 1, 3, 700):
        h.observe(v)
    assert h.count == 4 and h.total == 704
    assert h.vmin == 0 and h.vmax == 700
    assert h.mean == pytest.approx(176.0)
    assert sum(h.counts) == 4
    assert reg.names("serve.") == ["serve.queue_depth", "serve.steps"]
    reg.reset(prefix="serve.")
    assert c.value == 0 and h.count == 0
    assert g.value == 2.5                    # outside the reset prefix


def test_registry_type_and_bounds_conflicts():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    reg.histogram("h", bounds=(1, 2))
    with pytest.raises(ValueError):
        reg.histogram("h", bounds=(1, 2, 3))
    with pytest.raises(ValueError):
        reg.histogram("bad", bounds=(2, 1))  # not strictly increasing


def test_snapshot_is_deep_and_non_aliasing():
    reg = MetricsRegistry()
    reg.counter("c").inc(3)
    reg.histogram("h").observe(2)
    s1 = reg.snapshot()
    s2 = reg.snapshot()
    # mutating a snapshot never perturbs the registry or other snapshots
    s1["c"]["value"] = 999
    s1["h"]["counts"][0] = 999
    s1["h"]["bounds"][0] = -1
    assert reg.counter("c").value == 3
    assert reg.histogram("h").counts[0] == 0
    assert s2["c"]["value"] == 3
    assert s2["h"]["counts"] is not s1["h"]["counts"]
    assert s2["h"]["bounds"][0] == DEFAULT_BOUNDS[0]


def _apply(ops):
    """Replay (kind, value) ops into a fresh registry, return snapshot."""
    reg = MetricsRegistry()
    for kind, v in ops:
        if kind == 0:
            reg.counter("c").inc(v)
        elif kind == 1:
            reg.gauge("g").set(v)
        else:
            reg.histogram("h").observe(v)
    return reg.snapshot()


def test_merge_deterministic_properties():
    a = _apply([(0, 3), (2, 5), (2, 5000)])
    b = _apply([(0, 4), (1, 7.0)])
    c = _apply([(2, 1)])
    # identity: merging one snapshot copies it (non-aliasing)
    m = merge_snapshots(a)
    assert m == a
    m["h"]["counts"][0] = 77
    assert a["h"]["counts"][0] != 77
    # commutative + associative over a mixed group
    ab_c = merge_snapshots(merge_snapshots(a, b), c)
    a_bc = merge_snapshots(a, merge_snapshots(b, c))
    cba = merge_snapshots(c, b, a)
    assert ab_c == a_bc == cba
    assert ab_c["c"]["value"] == 7
    assert ab_c["h"]["count"] == 3
    assert ab_c["h"]["min"] == 1 and ab_c["h"]["max"] == 5000
    # gauge: larger (seq, value) wins regardless of order
    g1 = _apply([(1, 5.0), (1, 2.0)])        # seq 2, value 2.0
    g2 = _apply([(1, 9.0)])                  # seq 1, value 9.0
    assert merge_snapshots(g1, g2)["g"]["value"] == 2.0
    assert merge_snapshots(g2, g1)["g"]["value"] == 2.0


def test_merge_type_and_bounds_mismatch_raise():
    with pytest.raises(ValueError):
        merge_snapshots({"x": {"type": "counter", "value": 1}},
                        {"x": {"type": "gauge", "value": 1, "seq": 1}})
    h1 = MetricsRegistry()
    h1.histogram("h", bounds=(1, 2)).observe(1)
    h2 = MetricsRegistry()
    h2.histogram("h", bounds=(1, 3)).observe(1)
    with pytest.raises(ValueError):
        merge_snapshots(h1.snapshot(), h2.snapshot())


@pytest.mark.hyp
@settings(max_examples=50, deadline=None)
@given(st.lists(st.lists(st.tuples(st.integers(0, 2),
                                   st.integers(0, 10_000)),
                         max_size=8),
                min_size=3, max_size=3),
       st.permutations([0, 1, 2]))
def test_merge_associative_and_order_independent(shard_ops, order):
    """Any grouping / ordering of per-shard snapshots merges to the same
    total — the property that makes the registry shardable."""
    snaps = [_apply(ops) for ops in shard_ops]
    left = merge_snapshots(merge_snapshots(snaps[0], snaps[1]), snaps[2])
    right = merge_snapshots(snaps[0], merge_snapshots(snaps[1], snaps[2]))
    permuted = merge_snapshots(*[snaps[i] for i in order])
    assert left == right
    # gauge values may legitimately differ across orders only when two
    # shards tie on seq; merge breaks the tie by value, making even that
    # deterministic — so full equality must hold
    assert left == permuted


# ---------------------------------------------------------------------------
# scheduler telemetry contract
# ---------------------------------------------------------------------------

def _drained_sched(tracer=None, n_req=3, **kw):
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    kw.setdefault("n_slots", 2)
    kw.setdefault("capacity", 64)
    kw.setdefault("buckets", (8, 16, 32))
    sched = ServeScheduler(params, cfg, tracer=tracer, **kw)
    reqs = [_request_material(seed=20 + i, n_ctx=3, k=3)
            for i in range(n_req)]
    rids = [sched.submit(ctx, cands) for ctx, cands in reqs]
    return sched, params, rids


def test_telemetry_keys_match_schema():
    sched, _, _ = _drained_sched()
    sched.run()
    tel = sched.telemetry()
    assert set(tel) == set(TELEMETRY_SCHEMA)


def test_reset_telemetry_zeroes_every_schema_key():
    """The reset contract is data, not prose: every key the schema marks
    resettable returns exactly its documented zero after
    ``reset_telemetry()``; config/state keys are left meaningful."""
    sched, _, _ = _drained_sched()
    sched.run()
    assert sched.telemetry()["steps"] > 0
    sched.reset_telemetry()
    tel = sched.telemetry()
    for key, spec in TELEMETRY_SCHEMA.items():
        if "reset" not in spec:
            continue                         # config/state: not resettable
        want = spec["reset"]
        if want == "zero_map":
            assert all(v == 0 for v in tel[key].values()), (key, tel[key])
        else:
            assert tel[key] == want, (key, tel[key], want)


def test_telemetry_snapshot_does_not_alias_scheduler_state():
    sched, _, _ = _drained_sched()
    sched.run()
    tel = sched.telemetry()
    tel["bucket_steps"][8] = 999_999
    tel["watchdog_rows"].append(7)
    tel["watchdog_stuck_rids"].append(7)
    fresh = sched.telemetry()
    assert fresh["bucket_steps"].get(8) != 999_999
    assert 7 not in fresh["watchdog_rows"]
    assert 7 not in fresh["watchdog_stuck_rids"]


# ---------------------------------------------------------------------------
# tracing the serving hot path
# ---------------------------------------------------------------------------

def test_scheduler_drain_traces_nested_spans_and_events():
    """Acceptance mirror of ``serve_bench --trace``: a drain must emit
    scheduler-step spans nesting the per-unit prefill-chunk/burst spans,
    plus admission and hot-swap instants, and the document must pass the
    schema gate CI runs."""
    tracer = SpanTracer()
    sched, params, rids = _drained_sched(tracer=tracer)
    sched.step()                             # some pre-swap progress
    sched.update_params(params, version=2)   # hot_swap instant mid-drain
    res = sched.run()
    assert set(res) == set(rids)

    doc = sched.tracer.to_chrome_trace()
    assert validate_chrome_trace(doc) == []
    evs = doc["traceEvents"]
    steps = [e for e in evs if e["ph"] == "X"
             and e["name"] == "scheduler.step"]
    units = [e for e in evs if e["ph"] == "X"
             and e["name"] in ("prefill_chunk", "burst")]
    instants = {e["name"] for e in evs if e["ph"] == "i"}
    assert steps and units
    assert {"submit", "admission", "hot_swap", "finish"} <= instants
    # every unit span nests (positionally, same thread) inside a step span
    for u in units:
        assert any(s["tid"] == u["tid"]
                   and s["ts"] <= u["ts"]
                   and u["ts"] + u["dur"] <= s["ts"] + s["dur"]
                   for s in steps), u
    # the dispatched step spans carry their jit bucket
    assert any("args" in s and "bucket" in s["args"] for s in steps)
    # and queue depth was emitted as a counter series
    assert any(e["ph"] == "C" and e["name"] == "queue_depth" for e in evs)


def _count_syncs(monkeypatch, tracer):
    """Drain a scheduler while counting host<->device sync points:
    np.asarray on device arrays + jax.block_until_ready."""
    counts = {"asarray": 0, "block": 0}
    real_asarray, real_block = np.asarray, jax.block_until_ready

    def counting_asarray(a, *args, **kw):
        if isinstance(a, jax.Array):
            counts["asarray"] += 1
        return real_asarray(a, *args, **kw)

    def counting_block(x):
        counts["block"] += 1
        return real_block(x)

    monkeypatch.setattr(np, "asarray", counting_asarray)
    monkeypatch.setattr(jax, "block_until_ready", counting_block)
    try:
        sched, _, rids = _drained_sched(tracer=tracer)
        res = sched.run()
    finally:
        monkeypatch.undo()
    scores = np.asarray([res[r].scores for r in rids])
    return counts, scores


def test_tracing_adds_zero_device_syncs(monkeypatch):
    """The hard requirement on the tentpole: with tracing enabled the
    serving hot path performs exactly the same device syncs as untraced
    (the one-step-behind harvest ``np.asarray`` stays the only one)."""
    base, scores0 = _count_syncs(monkeypatch, tracer=None)
    tr = SpanTracer()
    traced, scores1 = _count_syncs(monkeypatch, tracer=tr)
    assert traced == base, (traced, base)
    assert base["block"] == 0                # block only in warmup()
    assert base["asarray"] > 0               # harvest syncs happened
    np.testing.assert_array_equal(scores0, scores1)
    assert len(tr) > 0 and tr.dropped == 0


# ---------------------------------------------------------------------------
# trainer compile/steady split
# ---------------------------------------------------------------------------

def test_trainer_compile_vs_steady_split():
    params = {"w": np.zeros(2, np.float32)}
    state = init_train_state(params, OptimizerConfig(lr=1e-3))
    sleeps = iter([0.05, 0.002, 0.002, 0.002])

    def step_fn(state, batch, rng):
        time.sleep(next(sleeps))             # first "step" = compile
        return state, {"loss": np.float32(0.5)}

    tr = SpanTracer()
    trainer = Trainer(step_fn, state, log_every=100, tracer=tr)
    trainer.run(iter([{}] * 4), n_steps=4)
    t = trainer.timing()
    assert trainer.compile_s is not None and trainer.compile_s >= 0.05
    assert t["steady_steps"] == 3
    assert 0 < t["step_s"] < t["compile_s"]
    assert len(trainer.history) == 4
    spans = [e for e in tr.events() if e["name"] == "train.step"]
    assert [e["args"]["step"] for e in spans] == [1, 2, 3, 4]


class _Loss:
    """A step's loss whose wait and read advance a manual clock."""

    def __init__(self, clk, wait_s, value=0.5):
        self.clk, self.wait_s, self.value = clk, wait_s, value

    def block_until_ready(self):
        self.clk.advance(self.wait_s)
        return self

    def __float__(self):
        self.clk.advance(0.001)
        return self.value


def _batches(n, rows=2, width=8):
    rng = np.random.default_rng(1)
    out = []
    for _ in range(n):
        valid = rng.random((rows, width)) < 0.7
        out.append({"is_sum": valid & (rng.random((rows, width)) < 0.3),
                    "valid": valid})
    return out


def test_trainer_host_spans_and_counters():
    """Each step: feed beside train.step, dispatch and wait inside it,
    fetch after it, with exact times; the counters equal the batches'
    targets, row tokens and pad tokens."""
    clk = ManualClock()
    tr = SpanTracer(clock=clk)
    state = init_train_state({"w": np.zeros(2, np.float32)},
                             OptimizerConfig(lr=1e-3))

    def step_fn(state, batch, rng):
        clk.advance(0.010)                   # dispatch
        return state, {"loss": _Loss(clk, 0.200)}

    def feed(batches):
        for b in batches:
            clk.advance(0.003)
            yield b

    bs = _batches(3)
    trainer = Trainer(step_fn, state, log_every=100, tracer=tr)
    trainer.run(feed(bs), n_steps=3)
    ev = {(e["name"], e["args"]["step"]): e for e in tr.events()
          if e["name"].startswith("train.")}
    assert {n for n, _ in ev} == {"train.feed", "train.step",
                                  "train.dispatch", "train.wait",
                                  "train.fetch"}

    def at(name, step):
        e = ev[(name, step)]
        return e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6

    t = 0.0
    for n in (1, 2, 3):
        assert at("train.feed", n) == pytest.approx((t, t + 0.003))
        a = t + 0.003
        assert at("train.step", n) == pytest.approx((a, a + 0.210))
        assert at("train.dispatch", n)[0] == pytest.approx(a)
        assert at("train.dispatch", n)[1] - a == pytest.approx(0.010, abs=1e-3)
        assert at("train.wait", n) == pytest.approx((a + 0.010, a + 0.210))
        assert at("train.fetch", n) == pytest.approx((a + 0.210, a + 0.211))
        t = a + 0.211
    snap = trainer.metrics.snapshot("train.")
    assert snap["train.steps"]["value"] == 3
    assert snap["train.targets"]["value"] == sum(int(b["is_sum"].sum())
                                                 for b in bs)
    assert snap["train.tokens"]["value"] == sum(b["valid"].size for b in bs)
    assert snap["train.pad_tokens"]["value"] == sum(int((~b["valid"]).sum())
                                                    for b in bs)


def _count_train_syncs(monkeypatch, tracer):
    """Device syncs of a trainer run: jax.block_until_ready calls,
    np.asarray and float() of device arrays."""
    import jax.numpy as jnp
    from jax._src.array import ArrayImpl
    counts = {"asarray": 0, "block": 0, "float": 0}
    real_asarray, real_block = np.asarray, jax.block_until_ready
    real_float = ArrayImpl.__float__

    def counting_asarray(a, *args, **kw):
        if isinstance(a, jax.Array):
            counts["asarray"] += 1
        return real_asarray(a, *args, **kw)

    def counting_block(x):
        counts["block"] += 1
        return real_block(x)

    def counting_float(self):
        counts["float"] += 1
        return real_float(self)

    @jax.jit
    def step_fn(state, batch, rng):
        loss = jnp.sum(state.params["w"] ** 2)
        return state, {"loss": loss, "grad_norm": loss * 2}

    state = init_train_state({"w": np.ones(2, np.float32)},
                             OptimizerConfig(lr=1e-3))
    step_fn(state, {}, jax.random.PRNGKey(0))        # compile outside
    monkeypatch.setattr(np, "asarray", counting_asarray)
    monkeypatch.setattr(jax, "block_until_ready", counting_block)
    monkeypatch.setattr(ArrayImpl, "__float__", counting_float)
    try:
        trainer = Trainer(step_fn, state, log_every=100, tracer=tracer)
        trainer.run(iter([{}] * 3), n_steps=3)
    finally:
        monkeypatch.undo()
    return counts


def test_trainer_spans_add_zero_device_syncs(monkeypatch):
    """One wait and one read per metric a step, traced or not (the
    counters read only host arrays)."""
    base = _count_train_syncs(monkeypatch, tracer=None)
    traced = _count_train_syncs(monkeypatch, tracer=SpanTracer())
    assert base == traced == {"asarray": 0, "block": 3, "float": 6}


def test_compile_watch_names_a_recompile():
    """A new shape recompiles: one ``jit.compile`` span in the tracer and
    ``jit.compiles`` + 1 in the owner's registry."""
    tr = SpanTracer()
    state = init_train_state({"w": np.zeros(2, np.float32)},
                             OptimizerConfig(lr=1e-3))
    trainer = Trainer(lambda s, b, r: (s, {"loss": np.float32(0)}), state,
                      tracer=tr)
    f = jax.jit(lambda x: x * 3 + 1)
    f(np.ones((3, 5), np.float32)).block_until_ready()
    n0 = trainer.metrics.counter("jit.compiles").value
    s0 = trainer.metrics.counter("jit.compile_s").value
    tr.clear()
    f(np.ones((7, 11), np.float32)).block_until_ready()
    spans = [e for e in tr.events() if e["name"] == "jit.compile"]
    assert len(spans) == 1 and spans[0]["dur"] > 0
    assert trainer.metrics.counter("jit.compiles").value == n0 + 1
    assert trainer.metrics.counter("jit.compile_s").value > s0
    f(np.ones((7, 11), np.float32)).block_until_ready()     # cached
    assert trainer.metrics.counter("jit.compiles").value == n0 + 1


def test_warmup_compile_s_is_the_compile_watch_measure():
    """``jit_stats()`` ``compile_s`` is the compile watch's seconds of the
    bucket's first call (every bucket compiles cold here), and their sum
    is the registry's ``jit.compile_s`` over warmup's decode calls."""
    sched, _, _ = _drained_sched(buckets=(8, 16))
    total0 = sched.metrics.counter("jit.compile_s").value
    n0 = sched.metrics.counter("jit.compiles").value
    sched.warmup()
    st = sched.jit_stats()
    assert set(st) == {8, 16}
    for s in st.values():
        assert set(s) == {"compile_s", "execute_s", "first_s"}
        assert 0 < s["compile_s"] <= s["first_s"]
    assert sched.metrics.counter("jit.compiles").value >= n0 + 2
    assert sum(s["compile_s"] for s in st.values()) <= (
        sched.metrics.counter("jit.compile_s").value - total0)


def test_annotated_spans_reach_the_profiler(tmp_path):
    """``jax_annotate=True``: every span is a TraceAnnotation, so a
    profiler trace holds the trainer's span names on its host plane."""
    import glob
    from jax.profiler import ProfileData
    tr = SpanTracer(jax_annotate=True)
    state = init_train_state({"w": np.zeros(2, np.float32)},
                             OptimizerConfig(lr=1e-3))
    trainer = Trainer(lambda s, b, r: (s, {"loss": np.float32(0.5)}), state,
                      log_every=100, tracer=tr)
    with jax.profiler.trace(str(tmp_path)):
        trainer.run(iter(_batches(2)), n_steps=2)
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:") for ln in p.lines
             for e in ln.events}
    assert {"train.feed", "train.step", "train.dispatch", "train.wait",
            "train.fetch"} <= names


# ---------------------------------------------------------------------------
# obs_report CLI
# ---------------------------------------------------------------------------

def test_obs_report_summarize_and_cli(tmp_path, capsys):
    clk = ManualClock()
    tr = SpanTracer(clock=clk)
    for _ in range(3):
        with tr.span("scheduler.step"):
            clk.advance(0.002)
        tr.instant("admission", rid=1)
        tr.counter("queue_depth", 2)
    s = summarize(tr.to_chrome_trace())
    assert s["spans"]["scheduler.step"]["count"] == 3
    assert s["spans"]["scheduler.step"]["mean_ms"] == pytest.approx(2.0)
    assert s["instants"] == {"admission": 3}
    assert s["counters_last"] == {"queue_depth": 2}
    assert s["dropped_events"] == 0

    path = tmp_path / "trace.json"
    out_json = tmp_path / "summary.json"
    tr.save(str(path))
    assert obs_report_main([str(path), "--json", str(out_json)]) == 0
    assert "scheduler.step" in capsys.readouterr().out
    assert json.loads(out_json.read_text())["instants"] == {"admission": 3}


def test_obs_report_rejects_malformed(tmp_path, capsys):
    broken = tmp_path / "broken.json"
    broken.write_text("{not json")
    assert obs_report_main([str(broken)]) == 1
    invalid = tmp_path / "invalid.json"
    invalid.write_text(json.dumps({"traceEvents": [{"ph": "Z"}]}))
    assert obs_report_main([str(invalid)]) == 1
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"traceEvents": []}))
    assert obs_report_main([str(empty)]) == 1
    capsys.readouterr()
