"""Observability subsystem: instruments, spans, exports, and the
instrumented hot paths (ops launch accounting, PlanCache/solver
mirrors, serving histograms, obs_report smoke)."""
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.cb_matrix import CBMatrix
from repro.core.streams import build_streams, build_super_streams
from repro.data import matrices
from repro.kernels import ops
from repro.obs import metrics as obs_metrics
from repro.solvers import CBLinearOperator, robust_solve


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts enabled on the real clock with empty stores."""
    obs.configure(enabled=True, clock=time.monotonic)
    obs.reset()
    yield
    obs.configure(enabled=True, clock=time.monotonic)
    obs.reset()


class FakeClock:
    def __init__(self, step=1.0):
        self.t = 0.0
        self.step = step

    def __call__(self):
        t, self.t = self.t, self.t + self.step
        return t


def _spd_op(d=96, seed=3, plan=None):
    r, c, v = matrices.spd_banded(d, bandwidth=7, seed=seed)
    cb = CBMatrix.from_coo(r, c, v.astype(np.float32), (d, d),
                           block_size=16, val_dtype=np.float32)
    return cb, CBLinearOperator.from_cb(cb, plan=plan)


# -- counters ---------------------------------------------------------------

def test_counter_monotonic_and_labeled():
    ctr = obs.counter("t.count")
    ctr.inc()
    ctr.inc(2, solver="cg")
    ctr.inc(3, solver="cg")
    ctr.inc(5, solver="gmres")
    assert ctr.value() == 1
    assert ctr.value(solver="cg") == 5
    assert ctr.value(solver="gmres") == 5
    assert ctr.total() == 11


def test_counter_rejects_negative_increment():
    with pytest.raises(ValueError, match="negative"):
        obs.counter("t.neg").inc(-1)


def test_counter_label_isolation():
    ctr = obs.counter("t.iso")
    ctr.inc(1, a="x")
    ctr.inc(1, a="y")
    assert ctr.value(a="x") == 1  # series never bleed into each other
    assert ctr.value(a="y") == 1
    assert ctr.value() == 0


def test_registry_kind_conflict_raises():
    obs.counter("t.kind")
    with pytest.raises(TypeError, match="already registered"):
        obs.gauge("t.kind")


def test_gauge_last_write_wins():
    g = obs.gauge("t.gauge")
    g.set(3)
    g.set(7)
    assert g.value() == 7


# -- histograms -------------------------------------------------------------

def test_histogram_bucket_edges_are_log2():
    # exact powers of two land in the bucket they bound from above
    for e in (-3, 0, 5):
        idx = obs_metrics.bucket_index(2.0 ** e)
        assert obs_metrics.BUCKET_EDGES[idx] == 2.0 ** e
    # a value just above an edge falls in the next bucket
    assert (obs_metrics.bucket_index(1.0001)
            == obs_metrics.bucket_index(1.0) + 1)
    # underflow (incl. 0) and overflow go to the sentinel buckets
    assert obs_metrics.bucket_index(0.0) == 0
    assert obs_metrics.bucket_index(-5.0) == 0
    assert (obs_metrics.bucket_index(2.0 ** 40)
            == len(obs_metrics.BUCKET_EDGES))


def test_histogram_deterministic_percentiles():
    h = obs.histogram("t.hist")
    for v in (0.3, 0.4, 0.6, 0.9, 100.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 5
    assert s["min"] == 0.3
    assert s["max"] == 100.0
    # rank-3 of 5 observations: 0.6 lives in the (0.5, 1.0] bucket
    assert s["p50"] == 1.0
    # p99 -> rank 5 -> the 100.0 observation, bucket edge 128
    assert s["p99"] == 128.0
    # identical multiset in any order -> identical summary
    h2 = obs.histogram("t.hist2")
    for v in (100.0, 0.9, 0.3, 0.6, 0.4):
        h2.observe(v)
    assert h2.summary() == s


def test_histogram_empty_summary_is_zero():
    assert obs.histogram("t.empty").summary()["count"] == 0


# -- snapshot / reset -------------------------------------------------------

def test_snapshot_roundtrips_json_and_sorts():
    obs.counter("t.b").inc(2, z="1", a="2")
    obs.counter("t.a").inc()
    obs.gauge("t.g").set(1.5)
    obs.histogram("t.h").observe(0.25)
    snap = obs.snapshot()
    assert list(snap) == sorted(snap)
    again = json.loads(json.dumps(snap))
    assert again == snap
    assert snap["t.b"]["series"][0]["labels"] == {"a": "2", "z": "1"}
    assert snap["t.h"]["series"][0]["summary"]["count"] == 1


def test_reset_clears_series_keeps_instruments():
    ctr = obs.counter("t.reset")
    ctr.inc(4)
    obs.reset()
    assert ctr.value() == 0
    assert obs.counter("t.reset") is ctr
    assert "t.reset" not in obs.snapshot()  # empty series omitted


# -- disabled mode ----------------------------------------------------------

def test_disabled_mode_is_a_noop():
    obs.configure(enabled=False)
    obs.counter("t.off").inc(5)
    obs.gauge("t.off.g").set(1)
    obs.histogram("t.off.h").observe(2.0)
    with obs.span("t.off.span") as sp:
        sp.set(k=1)
    assert obs.snapshot() == {}
    assert obs.tracer().records() == ()
    obs.configure(enabled=True)
    obs.counter("t.off").inc()
    assert obs.counter("t.off").value() == 1


# -- spans ------------------------------------------------------------------

def test_span_nesting_depth_and_attrs():
    clock = FakeClock()
    obs.configure(clock=clock)
    with obs.span("outer", phase="a"):
        with obs.span("inner") as sp:
            sp.set(status="ok")
    recs = {r.name: r for r in obs.tracer().records()}
    assert recs["outer"].depth == 0
    assert recs["inner"].depth == 1
    assert recs["inner"].attrs == {"status": "ok"}
    assert recs["inner"].start >= recs["outer"].start


def test_span_records_its_parent_id():
    with obs.span("root"):
        with obs.span("child"):
            with obs.span("grandchild"):
                pass
        with obs.span("child2"):
            pass
    with obs.span("root2"):
        pass
    recs = {r.name: r for r in obs.tracer().records()}
    assert len({r.span_id for r in recs.values()}) == 5
    assert recs["root"].parent is None and recs["root2"].parent is None
    assert recs["child"].parent == recs["root"].span_id
    assert recs["child2"].parent == recs["root"].span_id
    assert recs["grandchild"].parent == recs["child"].span_id


def test_summary_self_time_subtracts_direct_children_only():
    obs.configure(clock=FakeClock())   # every clock read advances 1 s
    with obs.span("a"):                # a: 0 .. 7
        with obs.span("b"):            # b: 1 .. 4
            with obs.span("c"):        # c: 2 .. 3
                pass
        with obs.span("b"):            # b: 5 .. 6
            pass
    rows = {r["name"]: r for r in obs.tracer().summary()}
    assert rows["a"]["total_s"] == 7.0 and rows["a"]["self_s"] == 3.0
    assert rows["b"]["total_s"] == 4.0 and rows["b"]["self_s"] == 3.0
    assert rows["c"]["total_s"] == 1.0 and rows["c"]["self_s"] == 1.0


def test_obs_imports_without_jax():
    import pathlib
    import subprocess
    import sys

    code = ("import sys; import repro.obs as o\n"
            "with o.span('x'):\n    pass\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n"
            "assert o.tracer().records()[0].name == 'x'")
    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env={"PYTHONPATH": str(src), "PATH": ""})
    assert out.returncode == 0, out.stderr


def test_span_appears_on_the_profilers_host_plane(tmp_path):
    """With jax imported, a span is also a ``TraceAnnotation``: a profile
    taken around it shows it on a host plane, on the device trace's clock."""
    import pathlib

    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("cb.test_span"):
            jnp.ones(3).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = pathlib.Path(tmp_path).rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    host = [ev.name for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]
    assert "cb.test_span" in host
    (rec,) = obs.tracer().records()
    assert rec.name == "cb.test_span"


def test_span_records_error_attr():
    with pytest.raises(RuntimeError):
        with obs.span("boom"):
            raise RuntimeError("x")
    (rec,) = obs.tracer().records()
    assert rec.attrs["error"] == "RuntimeError"


def test_injectable_clock_makes_traces_deterministic():
    def run():
        obs.reset()
        obs.configure(clock=FakeClock())
        with obs.span("a"):
            with obs.span("b"):
                pass
        return obs.chrome_trace()

    assert run() == run()


def test_chrome_trace_schema(tmp_path):
    obs.configure(clock=FakeClock())
    with obs.span("work", n=3):
        pass
    path = obs.export_chrome_trace(tmp_path / "t.trace.json")
    with open(path) as f:
        trace = json.load(f)
    assert isinstance(trace["traceEvents"], list)
    (ev,) = trace["traceEvents"]
    assert ev["ph"] == "X"
    assert isinstance(ev["ts"], (int, float))
    assert isinstance(ev["dur"], (int, float))
    assert ev["name"] == "work"
    assert ev["args"] == {"n": 3, "depth": 0, "id": 0, "parent": None}


def test_tracer_bounded_buffer_counts_drops():
    t = obs.Tracer(max_spans=2)
    for _ in range(4):
        with t.span("s"):
            pass
    assert len(t.records()) == 2
    assert t.dropped == 2


# -- MirroredCounter --------------------------------------------------------

def test_mirrored_counter_feeds_registry_and_stays_local():
    mc = obs.MirroredCounter(metric="t.mirror", label="site")
    mc["cg"] += 1
    mc["cg"] += 1
    mc["gmres"] += 1
    assert dict(mc) == {"cg": 2, "gmres": 1}
    assert obs.counter("t.mirror").value(site="cg") == 2
    # registry reset does not disturb the local (legacy API) view
    obs.reset()
    mc["cg"] += 1
    assert mc["cg"] == 3
    assert obs.counter("t.mirror").value(site="cg") == 1
    # disabled: local keeps counting, registry frozen
    obs.configure(enabled=False)
    mc["cg"] += 1
    assert mc["cg"] == 4
    obs.configure(enabled=True)
    assert obs.counter("t.mirror").value(site="cg") == 1


# -- ops launch accounting --------------------------------------------------

def _small_cb(d=64, seed=2):
    r, c, v = matrices.banded(d, d, bandwidth=5, fill=0.8, seed=seed)
    return CBMatrix.from_coo(r, c, v.astype(np.float32), (d, d),
                             block_size=16, val_dtype=np.float32)


def test_launch_stats_match_built_streams():
    # flat-stream arithmetic must replicate the jit-side ``_regroup``
    # path exactly (that is what ``cb_spmv`` runs on SpMVStreams input);
    # packed-stream stats must agree with the stream's own padded_work.
    cb = _small_cb()
    flat = build_streams(cb)
    for G in (1, 2, 4):
        regrouped = ops._regroup(flat, G)
        from_flat = ops.spmv_launch_stats(flat, G)
        from_regrouped = ops.spmv_launch_stats(regrouped)
        assert from_flat["padded"] == from_regrouped["padded"]
        assert from_flat["steps"] == from_regrouped["steps"]
        packed = build_super_streams(cb, group_size=G)
        assert (ops.spmv_launch_stats(packed)["padded_total"]
                == sum(packed.padded_work().values()))


def test_cb_spmv_bit_identical_with_obs_on_and_off():
    cb = _small_cb()
    streams = build_super_streams(cb, group_size=2)
    x = jnp.asarray(np.random.default_rng(0)
                    .standard_normal(cb.shape[1]).astype(np.float32))
    y_on = np.asarray(ops.cb_spmv(streams, x))
    obs.configure(enabled=False)
    y_off = np.asarray(ops.cb_spmv(streams, x))
    np.testing.assert_array_equal(y_on, y_off)


def test_cb_spmv_records_per_format_accounting():
    cb = _small_cb()
    streams = build_super_streams(cb, group_size=2)
    x = jnp.zeros(cb.shape[1], jnp.float32)
    ops.cb_spmv(streams, x)
    stats = ops.spmv_launch_stats(streams)
    snap = obs.snapshot()
    for fmt, steps in stats["steps"].items():
        if not steps:
            continue
        series = {tuple(sorted(s["labels"].items())): s["value"]
                  for s in snap["repro.ops.spmv.steps"]["series"]}
        assert series[(("format", fmt),)] == steps
        padded = {tuple(sorted(s["labels"].items())): s["value"]
                  for s in snap["repro.ops.spmv.padded_elems"]["series"]}
        assert padded[(("format", fmt),)] == stats["padded"][fmt]
    assert snap["repro.ops.spmv.calls"]["series"][0]["value"] == 1


# -- migrated counters ------------------------------------------------------

def test_plan_cache_counters_mirror_to_registry(tmp_path):
    from repro.autotune import PlanCache, SearchSettings

    cache = PlanCache(tmp_path)
    settings = SearchSettings(mode="heuristic")
    r, c, v = matrices.spd_banded(96, bandwidth=7, seed=3)
    CBMatrix.plan_for(r, c, v.astype(np.float32), (96, 96), cache=cache,
                      settings=settings)
    CBMatrix.plan_for(r, c, v.astype(np.float32), (96, 96), cache=cache,
                      settings=settings)
    assert (cache.hits, cache.misses) == (1, 1)
    ctr = obs.counter("repro.autotune.plan_cache.lookups")
    assert ctr.value(outcome="hit") >= 1
    assert ctr.value(outcome="miss") >= 1


def test_trace_counts_mirror_to_registry():
    from repro.solvers import krylov as krylov_mod

    before = dict(krylov_mod._TRACE_COUNTS)
    _cb, op = _spd_op(seed=5)
    b = jnp.asarray(np.random.default_rng(1)
                    .standard_normal(96).astype(np.float32))
    res = robust_solve(op, b, tol=1e-6, maxiter=300, impl="reference")
    assert res.converged
    after = dict(krylov_mod._TRACE_COUNTS)
    assert after["cg"] >= before.get("cg", 0)
    assert isinstance(krylov_mod._TRACE_COUNTS, obs.MirroredCounter)


def test_robust_solve_emits_attempt_metrics():
    _cb, op = _spd_op(seed=7)
    b = jnp.asarray(np.random.default_rng(2)
                    .standard_normal(96).astype(np.float32))
    res = robust_solve(op, b, tol=1e-6, maxiter=300, impl="reference")
    assert res.converged
    assert obs.counter("repro.solvers.robust.calls").total() == 1
    attempts = obs.counter("repro.solvers.robust.attempts")
    assert attempts.total() == len(res.attempts)
    outcome = obs.counter("repro.solvers.robust.outcome")
    assert outcome.value(outcome="converged", solver=res.solver) == 1
    names = [r.name for r in obs.tracer().records()]
    assert "robust_solve" in names
    assert f"solve:{res.solver}" in names


# -- serving ----------------------------------------------------------------

def _tiny_engine(**kw):
    import jax

    from repro.configs.base import ModelConfig
    from repro.models.model import Model
    from repro.serving import ServingEngine

    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=32,
                      num_heads=2, num_kv_heads=1, d_ff=64, vocab_size=128,
                      attn_chunk=32, remat="none", dtype="float32")
    model = Model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    return ServingEngine(model, params, slots=2, max_len=64, **kw)


def test_serving_health_histograms_and_backoff():
    from repro.serving import Request

    sleeps = []
    eng = _tiny_engine(max_step_retries=2, retry_backoff_s=0.5,
                       sleep=sleeps.append)
    fail = {"n": 2}
    orig = eng.step_fn

    def flaky(params, state, tokens, pos):
        if fail["n"]:
            fail["n"] -= 1
            raise RuntimeError("injected step fault")
        return orig(params, state, tokens, pos)

    eng.step_fn = flaky
    eng.submit(Request(uid=0, prompt=np.array([1], np.int32),
                       max_new_tokens=2))
    eng.run_until_done(max_ticks=16)
    h = eng.health()
    assert h["retries"] == 2
    # exponential backoff: 0.5 * 2^0 + 0.5 * 2^1, accumulated exactly
    assert h["backoff_total_s"] == pytest.approx(1.5)
    assert sleeps == [0.5, 1.0]
    assert h["deadline_miss_count"] == h["deadline_expired"] == 0
    assert h["tick_latency_s"]["count"] == h["ticks"] > 0
    assert h["queue_depth_hist"]["count"] == h["ticks"]
    assert obs.counter("repro.serving.ticks").total() == h["ticks"]
    names = [r.name for r in obs.tracer().records()]
    assert "serving.tick" in names


def test_serving_health_keeps_legacy_keys_when_disabled():
    from repro.serving import Request

    obs.configure(enabled=False)
    eng = _tiny_engine()
    eng.submit(Request(uid=0, prompt=np.array([1], np.int32),
                       max_new_tokens=1))
    eng.run_until_done(max_ticks=8)
    h = eng.health()
    for key in ("ticks", "queue_depth", "active_slots", "completed",
                "rejected", "retries", "deadline_expired", "last_error"):
        assert key in h
    assert h["completed"] == 1
    assert h["tick_latency_s"]["count"] == 0
    assert obs.snapshot() == {}


# -- obs_report smoke (tier-1) ----------------------------------------------

def test_obs_report_exports_valid_chrome_trace(tmp_path, capsys):
    import sys

    sys.path.insert(0, "scripts")
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    out = tmp_path / "demo.trace.json"
    payload = obs_report.main(["--out", str(out)])
    with open(out) as f:
        trace = json.load(f)
    assert trace == payload["trace"]
    events = trace["traceEvents"]
    assert isinstance(events, list) and events
    for ev in events:
        assert ev["ph"] == "X"
        assert isinstance(ev["ts"], (int, float))
        assert isinstance(ev["dur"], (int, float))
    names = {ev["name"] for ev in events}
    assert "robust_solve" in names
    assert "serving.tick" in names
    snap = payload["snapshot"]
    assert "repro.ops.spmv.calls" in snap
    assert "self_ms" in capsys.readouterr().out
