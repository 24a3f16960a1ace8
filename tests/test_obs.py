"""Observability layer (DESIGN.md §11).

Covers: the streaming histogram's bucket/percentile math (boundary
exactness, degenerate streams, merge), registry semantics (idempotent
creation, kind collisions, the disabled null path), snapshot export +
the hand-rolled validator's rejections, Prometheus text exposition,
the JSONL trace log, the StragglerMonitor's O(1)-memory contract, and
the serve engine integration — including the FROZEN ``stats()`` /
snapshot key sets for both the plain and speculative engines (the
report surface scripts and CI consume).
"""
import io
import json
import math

import numpy as np
import pytest

from repro.obs import export
from repro.obs.metrics import NULL_REGISTRY, Histogram, Registry
from repro.obs.trace import TraceLog


# ---------------------------------------------------------------------------
# Histogram math
# ---------------------------------------------------------------------------
class TestHistogram:
    def test_boundary_values_are_upper_inclusive(self):
        """A sample exactly on boundaries[i] lands in bucket i (Prometheus
        ``le`` semantics), deterministically — no float-log ambiguity."""
        h = Histogram("t", lo=1.0, growth=2.0, n_buckets=4)
        assert h.boundaries == [1.0, 2.0, 4.0, 8.0]
        for v, bucket in ((1.0, 0), (2.0, 1), (4.0, 2), (8.0, 3)):
            h.record(v)
            assert h.counts[bucket] == 1, f"{v} should land in bucket {bucket}"
            h.counts[bucket] = 0
        h.record(0.5)  # below lo -> bucket 0
        assert h.counts[0] == 1
        h.record(2.0000001)  # just past a boundary -> next bucket
        assert h.counts[2] == 1
        h.record(9.0)  # past the last boundary -> overflow
        assert h.counts[4] == 1

    def test_empty(self):
        h = Histogram("t", lo=1.0, growth=2.0, n_buckets=4)
        assert h.count == 0 and h.percentile(50) is None and h.mean is None
        j = h.to_json()
        assert j["min"] is None and j["p99"] is None and j["count"] == 0

    def test_one_sample_percentiles_exact(self):
        h = Histogram("t", lo=1e-6, growth=2.0 ** 0.25, n_buckets=105)
        h.record(0.0371)
        for q in (0, 50, 90, 99, 100):
            assert h.percentile(q) == 0.0371

    def test_all_equal_exact(self):
        h = Histogram("t")
        for _ in range(1000):
            h.record(2.5e-3)
        assert h.percentile(50) == 2.5e-3 and h.percentile(99) == 2.5e-3

    def test_percentiles_monotone_and_bounded(self):
        rng = np.random.default_rng(3)
        samples = rng.lognormal(mean=-6.0, sigma=1.5, size=5000)
        h = Histogram("t")
        for v in samples:
            h.record(float(v))
        p50, p90, p99 = h.percentile(50), h.percentile(90), h.percentile(99)
        assert h.min <= p50 <= p90 <= p99 <= h.max
        # relative error bounded by one growth factor vs the true quantile
        for q, got in ((50, p50), (90, p90), (99, p99)):
            true = float(np.quantile(samples, q / 100.0))
            assert true / h.growth <= got <= true * h.growth

    def test_count_sum_min_max_exact(self):
        h = Histogram("t")
        vals = [0.5, 1.5, 2.5, 0.25]
        for v in vals:
            h.record(v)
        assert h.count == 4 and h.total == pytest.approx(sum(vals))
        assert h.min == 0.25 and h.max == 2.5 and h.mean == pytest.approx(sum(vals) / 4)

    def test_merge_equals_single_stream(self):
        a, b, both = (Histogram("t", lo=1e-3, growth=2.0, n_buckets=16) for _ in range(3))
        rng = np.random.default_rng(5)
        for i, v in enumerate(rng.uniform(1e-4, 10.0, size=200)):
            (a if i % 2 else b).record(float(v))
            both.record(float(v))
        a.merge(b)
        assert a.counts == both.counts and a.count == both.count
        assert a.min == both.min and a.max == both.max
        assert a.total == pytest.approx(both.total)
        for q in (50, 90, 99):
            assert a.percentile(q) == both.percentile(q)

    def test_merge_layout_mismatch_raises(self):
        with pytest.raises(ValueError, match="different bucket layouts"):
            Histogram("a", lo=1.0, growth=2.0, n_buckets=4).merge(
                Histogram("b", lo=1.0, growth=2.0, n_buckets=5)
            )

    def test_bad_layout_raises(self):
        for lo, growth, n in ((0.0, 2.0, 4), (1.0, 1.0, 4), (1.0, 2.0, 0)):
            with pytest.raises(ValueError):
                Histogram("t", lo=lo, growth=growth, n_buckets=n)

    def test_overflow_bucket_percentile_uses_max(self):
        h = Histogram("t", lo=1.0, growth=2.0, n_buckets=2)  # boundaries [1, 2]
        h.record(100.0)
        h.record(250.0)
        assert h.counts[2] == 2 and h.percentile(99) == 250.0


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_idempotent_creation(self):
        reg = Registry(enabled=True)
        assert reg.counter("a") is reg.counter("a")
        assert reg.gauge("b") is reg.gauge("b")
        assert reg.histogram("c") is reg.histogram("c")

    def test_kind_collision_raises(self):
        reg = Registry(enabled=True)
        reg.counter("x")
        with pytest.raises(ValueError, match="another kind"):
            reg.gauge("x")
        with pytest.raises(ValueError, match="another kind"):
            reg.histogram("x")

    def test_histogram_layout_conflict_raises(self):
        reg = Registry(enabled=True)
        reg.histogram("h", lo=1.0, growth=2.0, n_buckets=8)
        with pytest.raises(ValueError, match="bucket layout"):
            reg.histogram("h", lo=1.0, growth=2.0, n_buckets=9)

    def test_disabled_registry_is_noop(self):
        reg = Registry(enabled=False)
        c, g, h = reg.counter("a"), reg.gauge("b"), reg.histogram("c")
        c.inc(5.0)
        g.set(3.0)
        h.record(1.0)
        assert c.value == 0.0 and g.value == 0.0 and h.count == 0
        # shared null instruments, nothing registered
        assert reg.counter("other") is c
        assert not reg.counters() and not reg.gauges() and not reg.histograms()

    def test_null_registry_singleton_disabled(self):
        assert not NULL_REGISTRY.enabled
        NULL_REGISTRY.counter("x").inc()
        assert not NULL_REGISTRY.counters()


# ---------------------------------------------------------------------------
# Export: snapshot + validator + Prometheus text
# ---------------------------------------------------------------------------
def _filled_registry() -> Registry:
    reg = Registry(enabled=True)
    reg.counter("serve.tokens_total").inc(42)
    reg.gauge("serve.queue_depth").set(3)
    h = reg.histogram("serve.ttft_s")
    for v in (0.01, 0.02, 0.04):
        h.record(v)
    reg.histogram("empty.hist")
    return reg


class TestExport:
    def test_snapshot_validates_and_roundtrips(self, tmp_path):
        doc = export.snapshot(_filled_registry())
        export.validate_snapshot(doc)
        path = str(tmp_path / "snap.json")
        export.write_snapshot(_filled_registry(), path)
        loaded = export.load_snapshot(path)
        assert loaded == doc
        assert doc["schema_version"] == export.SNAPSHOT_VERSION
        assert doc["counters"]["serve.tokens_total"] == 42
        assert doc["histograms"]["serve.ttft_s"]["count"] == 3
        assert doc["histograms"]["empty.hist"]["p50"] is None

    @pytest.mark.parametrize(
        "mutate, msg",
        [
            (lambda d: d.update(schema_version=99), "schema_version"),
            (lambda d: d.update(kind="bogus"), "kind"),
            (lambda d: d.pop("gauges"), "missing key"),
            (lambda d: d["counters"].update(bad="str"), "must be a number"),
            (
                lambda d: d["histograms"]["serve.ttft_s"].update(count=7),
                "must sum to count",
            ),
            (
                lambda d: d["histograms"]["serve.ttft_s"]["counts"].append(0),
                "n_buckets \\+ 1",
            ),
            (
                lambda d: d["histograms"]["empty.hist"].update(p50=1.0),
                "must be null",
            ),
            (
                lambda d: d["histograms"]["serve.ttft_s"].update(min=None),
                "must be a number",
            ),
        ],
    )
    def test_validator_rejects_malformed(self, mutate, msg):
        doc = export.snapshot(_filled_registry())
        mutate(doc)
        with pytest.raises(export.SnapshotError, match=msg):
            export.validate_snapshot(doc)

    def test_prometheus_text(self):
        txt = export.prometheus_text(_filled_registry())
        assert "# TYPE serve_tokens_total counter" in txt
        assert "serve_tokens_total 42" in txt
        assert "serve_queue_depth 3" in txt
        assert "# TYPE serve_ttft_s histogram" in txt
        assert 'serve_ttft_s_bucket{le="+Inf"} 3' in txt
        assert "serve_ttft_s_count 3" in txt
        # cumulative bucket series is non-decreasing
        cum = [
            int(line.rsplit(" ", 1)[1])
            for line in txt.splitlines()
            if line.startswith("serve_ttft_s_bucket")
        ]
        assert cum == sorted(cum) and cum[-1] == 3

    def test_cli_validate(self, tmp_path):
        from repro.obs.__main__ import main

        path = str(tmp_path / "snap.json")
        export.write_snapshot(_filled_registry(), path)
        assert main(["--validate", path]) == 0
        bad = str(tmp_path / "bad.json")
        doc = export.snapshot(_filled_registry())
        doc["schema_version"] = 99
        with open(bad, "w") as f:
            json.dump(doc, f)
        assert main(["--validate", bad]) == 1


# ---------------------------------------------------------------------------
# TraceLog
# ---------------------------------------------------------------------------
class TestTraceLog:
    def test_in_memory_events(self):
        tl = TraceLog(sink=None)
        ev = tl.event("submit", rid=3, prompt_len=8)
        assert tl.events == [ev]
        assert ev["event"] == "submit" and ev["rid"] == 3 and ev["t"] >= 0

    def test_jsonl_sink(self, tmp_path):
        path = str(tmp_path / "events.jsonl")
        with TraceLog(sink=path) as tl:
            tl.event("submit", rid=0)
            tl.event("decode", live=2, dt_s=0.01)
        lines = [json.loads(x) for x in open(path).read().splitlines()]
        assert [e["event"] for e in lines] == ["submit", "decode"]
        assert lines[1]["rid"] is None and lines[1]["live"] == 2

    def test_file_like_sink(self):
        buf = io.StringIO()
        TraceLog(sink=buf).event("finish", rid=1, tokens=5)
        assert json.loads(buf.getvalue())["tokens"] == 5


# ---------------------------------------------------------------------------
# StragglerMonitor on the histogram primitive: O(1) memory
# ---------------------------------------------------------------------------
def test_straggler_monitor_memory_capped():
    from repro.runtime.straggler import StragglerMonitor

    mon = StragglerMonitor(window=50)
    for _ in range(1000):
        mon.record(0.01)
    assert len(mon._times) == 50  # capped at window, not 1000
    rep = mon.report()
    assert rep["steps"] == 1000 and mon.hist.count == 1000
    assert rep["p50_s"] == 0.01 and rep["p99_s"] == 0.01 and rep["max_s"] == 0.01
    assert rep["median_s"] == 0.01 and rep["straggle_events"] == 0


def test_straggler_monitor_event_list_capped():
    from repro.runtime.straggler import StragglerMonitor

    mon = StragglerMonitor(threshold=2.0, window=20)
    for _ in range(10):
        mon.record(0.01)
    for _ in range(100):  # sparse spikes: the window median stays ~0.01
        for _ in range(4):
            mon.record(0.01)
        mon.record(1.0)
    rep = mon.report()
    assert rep["straggle_events"] > 20  # running total survives the cap
    assert len(mon._events) <= 20


# ---------------------------------------------------------------------------
# Serve engine integration + FROZEN report schemas
# ---------------------------------------------------------------------------
jax = pytest.importorskip("jax")

from repro.configs.base import ArchConfig  # noqa: E402
from repro.serve import ServeEngine  # noqa: E402

CFG = ArchConfig(
    name="obs-t", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab=128, head_dim=16, dtype_str="float32",
)

STATS_KEYS = {
    "n_slots", "live_slots", "steps", "decode_steps", "prefills",
    "tokens_generated", "requests_completed", "requests_truncated",
    "mesh", "straggler", "energy_nj_per_token", "cache", "kernel_dispatch",
}
CACHE_KEYS = {
    "layout", "kv_bits", "page_size", "pages_total", "pages_used",
    "pages_shared", "prefix_hits", "bytes_per_token", "slot_bytes",
}
LATENCY_KEYS = {
    "ttft_p50_s", "ttft_p99_s", "itl_p50_s", "itl_p99_s",
    "request_p50_s", "request_p99_s",
}
STRAGGLER_KEYS = {
    "steps", "median_s", "straggle_events", "worst_ratio", "p50_s", "p99_s", "max_s",
}
SPECULATIVE_KEYS = {
    "spec_k", "drafter", "rounds", "tokens_drafted", "tokens_accepted",
    "acceptance_rate",
}


@pytest.fixture(scope="module")
def params():
    from repro.models import get_model

    return get_model(CFG).init_params(CFG, jax.random.PRNGKey(0))


def _reqs(sizes, news, seed=0):
    rng = np.random.default_rng(seed)
    return [
        (rng.integers(0, CFG.vocab, size=s).astype(np.int32), n)
        for s, n in zip(sizes, news)
    ]


def test_engine_metrics_and_frozen_stats(params):
    reqs = _reqs((8, 16, 24), (6, 4, 5))
    reg = Registry(enabled=True)
    tl = TraceLog(sink=None)
    eng = ServeEngine(CFG, params, n_slots=2, max_len=48, mesh=None,
                      metrics=reg, trace=tl)
    eng.serve(reqs)
    st = eng.stats()

    # FROZEN report schema (launch/serve.py and CI consume these keys)
    assert set(st) == STATS_KEYS | {"latency"}
    assert set(st["latency"]) == LATENCY_KEYS
    assert set(st["straggler"]) == STRAGGLER_KEYS
    assert set(st["cache"]) == CACHE_KEYS
    assert st["cache"]["layout"] == "dense" and st["cache"]["page_size"] == 0
    for shape, d in st["kernel_dispatch"].items():  # {} for float params
        assert set(d) == {"impl", "source", "blocks", "operand", "count"}, shape

    total_tokens = sum(n for _, n in reqs)
    h = reg.histograms()
    assert h["serve.ttft_s"].count == len(reqs)
    assert h["serve.request_s"].count == len(reqs)
    assert h["serve.itl_s"].count == total_tokens - len(reqs)
    c = reg.counters()
    assert c["serve.tokens_total"].value == total_tokens
    assert c["serve.requests_finished_total"].value == len(reqs)
    assert c["serve.energy_nj_total"].value == pytest.approx(
        st["energy_nj_per_token"] * total_tokens
    )
    # per-request spans: every lifecycle event traced
    names = [e["event"] for e in tl.events]
    assert names.count("submit") == len(reqs) and names.count("finish") == len(reqs)
    assert names.count("admit") == len(reqs) and "decode" in names
    for req_ev in (e for e in tl.events if e["event"] == "finish"):
        assert req_ev["tokens"] > 0 and req_ev["total_s"] > 0
    # the whole registry exports as a valid snapshot
    export.validate_snapshot(export.snapshot(reg))
    # straggler monitor saw every decode dispatch
    assert st["straggler"]["steps"] == st["decode_steps"]


def test_engine_disabled_registry_identical_output(params):
    reqs = _reqs((8, 16), (5, 4), seed=2)
    plain = ServeEngine(CFG, params, n_slots=2, max_len=32, mesh=None)
    instrumented = ServeEngine(
        CFG, params, n_slots=2, max_len=32, mesh=None, metrics=Registry(enabled=True)
    )
    outs_a = plain.serve(reqs)
    outs_b = instrumented.serve(reqs)
    for a, b in zip(outs_a, outs_b):
        np.testing.assert_array_equal(a, b)
    # disabled engine reports no latency block, no registered instruments
    st = plain.stats()
    assert "latency" not in st and set(st) == STATS_KEYS
    assert set(st["cache"]) == CACHE_KEYS
    assert st["energy_nj_per_token"] > 0


def test_speculative_engine_metrics_and_frozen_stats(params):
    reqs = _reqs((8, 14, 6), (8, 5, 10), seed=13)
    reg = Registry(enabled=True)
    eng = ServeEngine(CFG, params, n_slots=2, max_len=64, mesh=None,
                      spec_k=5, spec_draft="ngram", metrics=reg)
    eng.serve(reqs)
    st = eng.stats()
    assert set(st) == STATS_KEYS | {"latency", "speculative"}
    assert set(st["speculative"]) == SPECULATIVE_KEYS
    assert set(st["latency"]) == LATENCY_KEYS
    assert set(st["cache"]) == CACHE_KEYS

    h = reg.histograms()
    assert h["serve.spec.round_width"].count == st["speculative"]["rounds"]
    assert h["serve.spec.accepted_per_round"].count > 0
    assert h["serve.ttft_s"].count == len(reqs)
    assert reg.counters()["serve.tokens_total"].value == st["tokens_generated"]
    export.validate_snapshot(export.snapshot(reg))


def test_paged_engine_cache_stats_and_gauges(params):
    rng = np.random.default_rng(7)
    prefix = rng.integers(0, CFG.vocab, 16).astype(np.int32)
    reqs = [
        (np.concatenate([prefix, rng.integers(0, CFG.vocab, 4 + i)]).astype(np.int32), 5)
        for i in range(4)
    ]
    reg = Registry(enabled=True)
    eng = ServeEngine(CFG, params, n_slots=2, max_len=48, mesh=None,
                      kv_cache="paged", page_size=8, metrics=reg)
    eng.serve(reqs)
    st = eng.stats()
    assert set(st) == STATS_KEYS | {"latency"}
    cache = st["cache"]
    assert set(cache) == CACHE_KEYS
    assert cache["layout"] == "paged" and cache["page_size"] == 8
    # the 16-token shared prefix is 2 full pages; admissions overlapping
    # a live sharer acquire them instead of re-prefilling (once the last
    # reader finishes the pages are freed AND de-indexed, so a gap in
    # occupancy re-registers rather than hits — hence >=, not ==)
    assert cache["prefix_hits"] >= 4
    assert cache["pages_used"] == 0  # drained engine holds no pages
    # prefix sharing means a slot holds fewer private bytes than the
    # dense per-slot stripe (== bytes_per_token at float width)
    assert cache["slot_bytes"] < cache["bytes_per_token"]
    g = reg.gauges()
    assert "serve.cache.pages_used" in g and "serve.cache.pages_shared" in g
    assert reg.counters()["serve.cache.prefix_hits_total"].value == cache["prefix_hits"]
    export.validate_snapshot(export.snapshot(reg))


def test_engine_profile_hook(params, tmp_path):
    from repro.obs.trace import ProfileHook

    reqs = _reqs((8,), (6,), seed=4)
    hook = ProfileHook(str(tmp_path / "prof"), n_steps=2)
    eng = ServeEngine(CFG, params, n_slots=1, max_len=16, mesh=None, profile=hook)
    eng.serve(reqs)
    assert hook.done and not hook.active  # window closed (or stopped at drain)
    assert hook.seen >= 2


def test_math_boundary_reproducibility():
    """Boundary construction is deterministic: exp(i*log(g)) from ints."""
    a = Histogram("a", lo=1e-6, growth=2.0 ** 0.25, n_buckets=105)
    b = Histogram("b", lo=1e-6, growth=2.0 ** 0.25, n_buckets=105)
    assert a.boundaries == b.boundaries
    assert all(x < y for x, y in zip(a.boundaries, a.boundaries[1:]))
    assert a.boundaries[0] == 1e-6 and a.boundaries[-1] == pytest.approx(
        1e-6 * (2.0 ** 0.25) ** 104
    )
    assert math.isfinite(a.boundaries[-1])
