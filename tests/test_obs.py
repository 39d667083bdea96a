"""Observability layer: registry, spans, health, and the instrumented
hot paths' integration with them.

Covers the unit surface (`repro.obs.registry` / `trace` / `health`),
the export surfaces (snapshot, Prometheus text, CLI), the no-op
contract when disabled, and the serving-engine integration: health
state transitions (starting -> serving -> degraded -> serving),
recovery timing after a torn-tail WAL open, per-kind batcher latency
distributions (write barriers INCLUDED — the bug this PR fixed), and
`stats()` atomicity under a concurrent writer.
"""
from __future__ import annotations

import glob
import json
import os
import re
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.encoder import Embedder, EncoderConfig
from repro.obs.registry import bucket_index, bucket_upper
from repro.obs.health import (DEGRADED, SERVING, STARTING, STATE_VALUES,
                              HealthTracker)
from repro.obs.trace import render_tree
from repro.graph.edges import make_labels
from repro.graph.generators import sbm
from repro.serving.batcher import MicroBatcher
from repro.serving.engine import ServingEngine
from repro.serving.store import GraphStore


@pytest.fixture
def clean_obs():
    """Enabled layer with empty registry/ring; restores defaults."""
    obs.configure(enabled=True, trace_path="")
    obs.reset()
    yield
    obs.configure(enabled=True, trace_path="")
    obs.reset()


def _profiled_host_names(tmp_path, body) -> set:
    """Run `body` under a `jax.profiler` capture; the names of the
    events on the profile's /host:CPU plane."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    return {e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:CPU")
            for line in plane.lines for e in line.events}


def _pallas_fit_refit(rng):
    """A small interpret-mode pallas fit, then a refit."""
    g, truth = sbm(80, 3, 600, p_in=0.85, seed=int(rng.integers(1 << 31)))
    emb = Embedder(EncoderConfig(K=3, tile_n=32, edge_block=128),
                   backend="pallas")
    emb.fit(g, make_labels(80, 3, 0.3, rng, true_labels=truth))
    emb.refit(make_labels(80, 3, 0.6, rng, true_labels=truth))
    jax.block_until_ready(emb.Z_)


def _small_engine(rng, *, shards=2, n=60, data_dir=None, **kw):
    g, truth = sbm(n, 3, 600, p_in=0.85, seed=int(rng.integers(1 << 31)))
    Y = make_labels(n, 3, 0.5, rng, true_labels=truth)
    eng = ServingEngine(GraphStore(g, Y, 3), num_shards=shards,
                        data_dir=data_dir, **kw)
    return eng, truth


# -- registry ----------------------------------------------------------------

class TestRegistry:
    def test_counter_gauge_roundtrip(self, clean_obs):
        r = obs.registry()
        r.counter("repro_test_events_total", 2, kind="a")
        r.counter("repro_test_events_total", kind="a")
        r.counter("repro_test_events_total", kind="b")
        r.gauge("repro_test_rate_value", 7.5)
        assert r.counter_value("repro_test_events_total", kind="a") == 3
        assert r.counter_value("repro_test_events_total", kind="b") == 1
        assert r.counter_value("repro_test_events_total", kind="zz") == 0
        assert r.gauge_value("repro_test_rate_value") == 7.5
        snap = r.snapshot()
        assert snap["counters"]['repro_test_events_total{kind="a"}'] == 3
        assert snap["gauges"]["repro_test_rate_value"] == 7.5

    def test_name_scheme_enforced(self, clean_obs):
        r = obs.registry()
        for bad in ("plain", "repro_single", "Repro_x_y", "repro_x_Y",
                    "repro_x-y_z", "other_sub_metric"):
            assert not obs.valid_metric_name(bad)
            with pytest.raises(ValueError):
                r.counter(bad)
        assert obs.valid_metric_name("repro_serving_wal_append_seconds")

    def test_histogram_summary(self, clean_obs):
        r = obs.registry()
        vals = [0.001] * 98 + [0.5, 1.0]
        for v in vals:
            r.observe("repro_test_lat_seconds", v)
        s = r.hist_summary("repro_test_lat_seconds")
        assert s["count"] == 100
        assert s["sum"] == pytest.approx(sum(vals))
        assert s["min"] == pytest.approx(0.001)
        assert s["max"] == pytest.approx(1.0)
        # log2 buckets over-estimate by at most 2x, clamped to max
        assert 0.001 <= s["p50"] <= 0.002
        assert 0.5 <= s["p99"] <= 1.0            # within the 2x bound

    def test_bucket_layout(self):
        assert bucket_index(0.0) == 0
        assert bucket_index(1e-6) == 0
        assert bucket_index(1e300) == 63
        for v in (1e-5, 3e-3, 0.7, 42.0):
            i = bucket_index(v)
            assert v <= bucket_upper(i)
            assert i == 0 or v > bucket_upper(i - 1)

    def test_thread_safety_exact_counts(self, clean_obs):
        r = obs.registry()

        def hammer():
            for _ in range(1000):
                r.counter("repro_test_race_total")
                r.observe("repro_test_race_seconds", 1e-3)
        threads = [threading.Thread(target=hammer) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert r.counter_value("repro_test_race_total") == 8000
        assert r.hist_summary("repro_test_race_seconds")["count"] == 8000

    def test_prometheus_rendering(self, clean_obs):
        r = obs.registry()
        r.counter("repro_test_events_total", 3, kind="x")
        r.gauge("repro_test_rate_value", 2.0)
        for v in (1e-4, 1e-4, 0.3):
            r.observe("repro_test_lat_seconds", v)
        text = r.render_prometheus()
        assert "# TYPE repro_test_events_total counter" in text
        assert 'repro_test_events_total{kind="x"} 3' in text
        assert "# TYPE repro_test_lat_seconds histogram" in text
        assert 'repro_test_lat_seconds_bucket{le="+Inf"} 3' in text
        assert "repro_test_lat_seconds_count 3" in text
        # every sample line parses as  name{labels}? value
        sample = re.compile(
            r'^[a-z0-9_]+(\{[a-z0-9_]+="[^"]*"'
            r'(,[a-z0-9_]+="[^"]*")*\})? \S+$')
        for line in text.strip().splitlines():
            if line.startswith("#"):
                continue
            assert sample.match(line), line
        # cumulative bucket counts are non-decreasing and end at count
        cum = [int(ln.rsplit(" ", 1)[1])
               for ln in text.splitlines()
               if ln.startswith("repro_test_lat_seconds_bucket")]
        assert cum == sorted(cum) and cum[-1] == 3

    def test_summarize_pretty(self, clean_obs):
        obs.counter("repro_test_events_total")
        obs.observe("repro_test_lat_seconds", 0.01)
        out = obs.summarize(obs.snapshot())
        assert "repro_test_events_total" in out
        assert "p95" in out


# -- spans / tracing ---------------------------------------------------------

class TestSpans:
    def test_parent_links_and_attrs(self, clean_obs):
        with obs.span("outer", job="x") as so:
            with obs.span("inner") as si:
                si.set(rows=4)
        events = obs.trace_events()
        assert [e["name"] for e in events] == ["inner", "outer"]
        inner, outer = events
        assert inner["parent"] == outer["id"]
        assert outer["parent"] is None
        assert outer["attrs"] == {"job": "x"}
        assert inner["attrs"] == {"rows": 4}
        assert so.duration >= si.duration >= 0.0

    def test_error_capture(self, clean_obs):
        with pytest.raises(RuntimeError):
            with obs.span("boom"):
                raise RuntimeError("nope")
        (event,) = obs.trace_events()
        assert "nope" in event["error"]

    def test_metric_mirror(self, clean_obs):
        with obs.span("timed", metric="repro_test_span_seconds",
                      mlabels={"backend": "b"}):
            pass
        s = obs.registry().hist_summary("repro_test_span_seconds",
                                        backend="b")
        assert s["count"] == 1

    def test_ring_bounded(self, clean_obs):
        obs.configure(ring=8)
        try:
            for i in range(50):
                with obs.span(f"s{i}"):
                    pass
            events = obs.trace_events()
            assert len(events) == 8
            assert events[-1]["name"] == "s49"   # newest wins
        finally:
            obs.configure(ring=4096)

    def test_jsonl_sink_and_replay(self, clean_obs, tmp_path):
        path = str(tmp_path / "trace.jsonl")
        obs.configure(trace_path=path)
        with obs.span("parent"):
            with obs.span("child", shard=1):
                pass
        obs.configure(trace_path="")
        events = obs.load_jsonl(path)
        assert len(events) == 2
        for line in open(path):
            json.loads(line)                     # every line valid JSON
        tree = render_tree(events)
        lines = tree.splitlines()
        assert lines[0].startswith("- parent")
        assert lines[1].startswith("  - child")  # indented under parent
        assert "shard=1" in lines[1]

    def test_span_lands_on_profiler_host_plane(self, clean_obs,
                                               tmp_path):
        def body():
            with obs.span("encoder.probe", rows=3):
                with obs.span("encoder.inner"):
                    pass
        names = _profiled_host_names(tmp_path, body)
        assert {"encoder.probe", "encoder.inner"} <= names
        # attributes stay in the ring; the annotation is the name alone
        assert obs.trace_events()[-1]["attrs"] == {"rows": 3}

    def test_embed_layer_spans_on_profiler_host_plane(self, clean_obs,
                                                      tmp_path, rng):
        names = _profiled_host_names(tmp_path,
                                     lambda: _pallas_fit_refit(rng))
        assert {"encoder.plan", "encoder.fit", "encoder.gather",
                "encoder.scatter"} <= names
        ring = [e["name"] for e in obs.trace_events()]
        assert ring.count("encoder.gather") == 2     # fit and refit
        assert ring.count("encoder.scatter") == 2

    def test_exit_on_another_thread(self, clean_obs):
        sp = obs.span("encoder.handoff")
        sp.__enter__()
        errors = []

        def finish():
            try:
                sp.__exit__(None, None, None)
            except Exception as e:               # pragma: no cover
                errors.append(e)
        th = threading.Thread(target=finish)
        th.start()
        th.join()
        assert not errors
        assert [e["name"] for e in obs.trace_events()] == \
            ["encoder.handoff"]

    def test_orphan_renders_as_root(self, clean_obs):
        tree = render_tree([{"name": "lost", "id": 7, "parent": 99,
                             "t0": 1.0, "dur_s": 0.0}])
        assert tree.startswith("- lost")


# -- the disabled path -------------------------------------------------------

class TestDisabled:
    def test_true_noop(self, clean_obs):
        obs.configure(enabled=False)
        assert obs.tick() == 0.0 and obs.tock(0.0) == 0.0
        obs.counter("repro_test_events_total")
        obs.gauge("repro_test_rate_value", 1)
        obs.observe("repro_test_lat_seconds", 1)
        sp = obs.span("nothing", metric="repro_test_span_seconds")
        with sp as s:
            assert s.fence(123) == 123           # passes through, no block
        assert sp.duration == 0.0
        assert not obs.registry().series_names()
        assert not obs.trace_events()
        assert obs.snapshot()["enabled"] is False

    def test_fit_emits_nothing_when_off(self, clean_obs, rng):
        obs.configure(enabled=False)
        eng, _ = _small_engine(rng, shards=1)
        eng.query_embed([0, 1])
        eng.close()
        assert not obs.registry().series_names()

    def test_span_not_annotated_when_off(self, clean_obs, tmp_path):
        obs.configure(enabled=False)

        def body():
            with obs.span("encoder.probe"):
                pass
        assert "encoder.probe" not in _profiled_host_names(tmp_path, body)

    def test_fit_and_refit_leave_no_trace_when_off(self, clean_obs,
                                                   tmp_path, rng):
        obs.configure(enabled=False)
        names = _profiled_host_names(tmp_path,
                                     lambda: _pallas_fit_refit(rng))
        assert not obs.registry().series_names()
        assert not obs.trace_events()
        assert not [n for n in names if n.startswith("encoder.")]


# -- compile counter ---------------------------------------------------------

class TestCompileCounter:
    @staticmethod
    def _compiles():
        return obs.registry().counter_value("repro_jax_compiles_total")

    def test_counts_new_shapes_not_repeats(self, clean_obs):
        f = jax.jit(lambda x: x * 3.0 + 1.0)
        a, b = jnp.ones(5), jnp.ones(7)
        obs.reset()
        f(a)
        first = self._compiles()
        assert first >= 1
        f(a)
        assert self._compiles() == first         # cached: no compile
        f(b)
        assert self._compiles() > first          # a new shape compiles
        assert obs.registry().hist_summary(
            "repro_jax_compile_seconds")["count"] == self._compiles()

    def test_nothing_counted_when_off(self, clean_obs):
        obs.configure(enabled=False)
        jax.jit(lambda x: x - 2.0)(jnp.ones(3))
        assert not obs.registry().series_names()


# -- health state machine ----------------------------------------------------

class TestHealth:
    def test_transitions_and_export(self, clean_obs):
        h = HealthTracker("test")
        assert h.state == STARTING
        assert obs.registry().gauge_value("repro_test_health_state") \
            == STATE_VALUES[STARTING]
        assert h.to(SERVING) is True
        assert h.to(SERVING) is False            # idempotent
        assert h.to(DEGRADED, reason="disk") is True
        assert h.as_dict()["reason"] == "disk"
        assert obs.registry().counter_value(
            "repro_test_health_transitions_total", to=DEGRADED) == 1
        assert obs.registry().gauge_value("repro_test_health_state") \
            == STATE_VALUES[DEGRADED]
        h.to(SERVING)
        assert "reason" not in h.as_dict()

    def test_engine_serving_on_boot(self, clean_obs, rng):
        eng, _ = _small_engine(rng)
        assert eng.health()["state"] == SERVING
        eng.close()

    def test_engine_degrades_on_loop_error_and_recovers(self, clean_obs,
                                                        rng):
        eng, _ = _small_engine(rng)
        eng.loop_error = RuntimeError("checkpoint failed")
        h = eng.health()
        assert h["state"] == DEGRADED
        assert "checkpoint failed" in h["reason"]
        assert eng.stats()["health"]["state"] == DEGRADED
        eng.loop_error = None                    # fault cleared
        assert eng.health()["state"] == SERVING  # re-evaluated, not latched
        eng.close()

    def test_engine_degrades_on_slow_wal_append(self, clean_obs, rng,
                                                tmp_path):
        eng, _ = _small_engine(rng, data_dir=str(tmp_path / "d"),
                               degraded_append_s=0.05)
        eng.apply_edge_delta(np.array([0], np.int32),
                             np.array([1], np.int32),
                             np.ones(1, np.float32))
        assert eng.health()["state"] == SERVING  # a local append is fast
        eng.wal.last_append_seconds = 0.2        # simulated slow disk
        h = eng.health()
        assert h["state"] == DEGRADED and "wal append" in h["reason"]
        eng.wal.last_append_seconds = 1e-4
        assert eng.health()["state"] == SERVING
        eng.close()


# -- recovery timing (torn-tail WAL, as in test_wal_fuzz) --------------------

@pytest.mark.slow
def test_recovery_timed_after_torn_tail(clean_obs, rng, tmp_path):
    d = str(tmp_path / "dep")
    eng, _ = _small_engine(rng, data_dir=d)
    for _ in range(4):
        b = int(rng.integers(2, 20))
        eng.apply_edge_delta(rng.integers(0, 60, b).astype(np.int32),
                             rng.integers(0, 60, b).astype(np.int32),
                             rng.random(b).astype(np.float32) + 0.5)
    eng.close()
    wal_path = os.path.join(d, "wal-0.log")
    blob = open(wal_path, "rb").read()
    with open(wal_path, "wb") as f:              # crash mid-append
        f.write(blob[:len(blob) - 3])
    obs.reset()
    rec = ServingEngine.open(d)
    try:
        assert rec.health()["state"] == SERVING
        s = obs.registry().hist_summary("repro_serving_recovery_seconds")
        assert s["count"] == 1 and s["sum"] > 0.0
        assert obs.registry().counter_value(
            "repro_serving_recovery_replayed_total") == 3  # 4 - torn one
        names = [e["name"] for e in obs.trace_events()]
        assert "serving.recovery" in names
        assert "serving.rebuild" in names        # nested child ran
    finally:
        rec.close()


# -- batcher latency accounting (the satellite fix) --------------------------

class TestBatcherAccounting:
    def test_every_kind_lands_in_latency_histogram(self, clean_obs, rng):
        eng, truth = _small_engine(rng)
        mb = MicroBatcher(eng, topk=3)
        counts = {"embed": 3, "predict": 2, "topk": 2, "insert": 2,
                  "delete": 1, "labels": 1}
        for _ in range(counts["embed"]):
            mb.submit("embed", rng.integers(0, 60, 5))
        for _ in range(counts["predict"]):
            mb.submit("predict", rng.integers(0, 60, 5))
        for _ in range(counts["topk"]):
            mb.submit("topk", rng.integers(0, 60, 5))
        batch = (np.array([1, 2], np.int32), np.array([3, 4], np.int32),
                 np.ones(2, np.float32))
        for _ in range(counts["insert"]):
            mb.submit("insert", batch)
        mb.submit("delete", batch)
        mb.submit("labels", (np.arange(5), truth[:5]))
        served = mb.flush()
        assert served == sum(counts.values())
        # the distribution's count equals the submit count PER KIND —
        # write barriers are first-class citizens of the latency stats
        for kind, want in counts.items():
            s = obs.registry().hist_summary(
                "repro_serving_batcher_ticket_seconds", kind=kind)
            assert s["count"] == want, kind
            assert obs.registry().counter_value(
                "repro_serving_batcher_requests_total", kind=kind) == want
        eng.close()

    def test_failed_tickets_still_counted(self, clean_obs, rng):
        eng, _ = _small_engine(rng)
        mb = MicroBatcher(eng)
        t_bad = mb.submit("embed", np.array([10_000]))   # out of range
        t_ok = mb.submit("embed", np.array([0]))
        mb.flush()
        with pytest.raises(IndexError):
            t_bad.result(timeout=5)
        t_ok.result(timeout=5)
        s = obs.registry().hist_summary(
            "repro_serving_batcher_ticket_seconds", kind="embed")
        assert s["count"] == 2                   # errors are latencies too
        assert obs.registry().counter_value(
            "repro_serving_batcher_errors_total", kind="embed") == 1
        eng.close()


# -- stats() atomicity -------------------------------------------------------

def test_stats_atomic_under_concurrent_writes(clean_obs, rng):
    eng, truth = _small_engine(rng, shards=2)
    stop = threading.Event()
    errors = []

    def writer():
        try:
            i = 0
            while not stop.is_set():
                b = 4
                eng.apply_edge_delta(
                    np.arange(b, dtype=np.int32) % 60,
                    (np.arange(b, dtype=np.int32) + 1) % 60,
                    np.ones(b, np.float32))
                if i % 7 == 0:
                    eng.refresh()                # epoch also moves
                i += 1
        except Exception as e:                   # pragma: no cover
            errors.append(e)

    th = threading.Thread(target=writer)
    th.start()
    try:
        last_version = last_epoch = -1
        for _ in range(60):
            st = eng.stats()
            # lock-consistent snapshot: monotone counters, never torn
            assert st["version"] >= last_version
            assert st["epoch"] >= last_epoch
            assert st["deltas_applied"] >= 0
            assert st["health"]["state"] == SERVING
            assert st["metrics"]["enabled"] is True
            last_version, last_epoch = st["version"], st["epoch"]
    finally:
        stop.set()
        th.join()
        eng.close()
    assert not errors


# -- registry-backed engine stats / plan-cache counters ----------------------

def test_engine_stats_mirror_registry(clean_obs, rng, tmp_path):
    eng, truth = _small_engine(rng, data_dir=str(tmp_path / "d"))
    eng.apply_edge_delta(np.array([5], np.int32), np.array([6], np.int32),
                         np.ones(1, np.float32))
    eng.apply_label_delta(np.arange(3), truth[:3])
    eng.query_embed([0, 1, 2])
    eng.query_predict([3])
    eng.query_topk([4], k=2)
    eng.checkpoint()
    st = eng.stats()
    m = st["metrics"]
    assert m["counters"]["repro_serving_wal_records_total"
                         '{kind="edges"}'] == 1
    assert m["counters"]["repro_serving_delta_edges_total"] == 1
    assert m["counters"]['repro_serving_queries_total{kind="topk"}'] == 1
    assert m["counters"]["repro_serving_checkpoints_total"] == 1
    assert m["histograms"]["repro_serving_checkpoint_seconds"]["count"] \
        == 1
    # every shard reported its accumulator gauge (the owned-rows
    # memory contract as a live series)
    shard_gauges = [k for k in m["gauges"]
                    if k.startswith("repro_serving_shard_accumulator")]
    assert len(shard_gauges) == eng.num_shards
    # plan-cache events mirror the shards' identity-tier counters
    hits = obs.registry().counter_value(
        "repro_encoder_plan_cache_total", event="tier1_hit")
    built = obs.registry().counter_value(
        "repro_encoder_plan_cache_total", event="built")
    shard_plan = st["plan_stats"]
    assert built == shard_plan["built"] > 0
    assert hits == shard_plan["hits"]
    eng.close()


# -- CLI ---------------------------------------------------------------------

@pytest.mark.slow
def test_cli_snapshot_and_trace_replay(tmp_path):
    src_root = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "src")
    env = dict(os.environ,
               PYTHONPATH=src_root + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    trace = str(tmp_path / "demo.jsonl")
    out = subprocess.run(
        [sys.executable, "-m", "repro.obs", "--snapshot", "--json",
         "--trace-out", trace],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr
    snap = json.loads(out.stdout)
    series = (list(snap["counters"]) + list(snap["gauges"])
              + list(snap["histograms"]))
    for family in ("repro_serving_wal_", "repro_encoder_plan_cache",
                   "repro_serving_shard_", "repro_serving_batcher_",
                   "repro_kernel_"):
        assert any(family in s for s in series), family
    # replay the JSONL trace the demo wrote: parent-linked span tree
    replay = subprocess.run(
        [sys.executable, "-m", "repro.obs", "--trace", trace],
        capture_output=True, text=True, env=env, timeout=120)
    assert replay.returncode == 0, replay.stderr
    assert "- obs.demo" in replay.stdout
    assert "  - serving.rebuild" in replay.stdout   # indented child
