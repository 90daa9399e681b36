"""Unified-telemetry contracts: registry, tracing, trace-report, and the
acceptance drill - a supervised multi-chunk run whose trace's chunk
boundaries match the checkpoint rotation steps on disk.

The Prometheus exposition is validated with `parse_prometheus`, a
minimal line parser shared with tests/test_serve.py (which checks the
HTTP surface); here it pins the renderer itself: sample names, label
escaping, histogram triplets, and text/JSON agreement on shared state.
"""

import json
import os
import threading

import pytest

from wavetpu.obs import report as obs_report
from wavetpu.obs import telemetry, tracing
from wavetpu.obs.registry import MetricsRegistry, get_registry


def parse_prometheus(text, with_exemplars=False):
    """Minimal exposition-format parser: {sample_name_with_labels: float}
    plus {family: type}.  Raises on malformed lines, so using it IS the
    validity assertion.  `with_exemplars=True` additionally validates +
    returns the OpenMetrics exemplar suffixes (`name # {labels} value
    ts`) and the trailing `# EOF` marker as a third mapping
    {sample_name: {"labels": {...}, "value": float, "ts": float}}."""
    samples, types, exemplars = {}, {}, {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            continue
        if line == "# EOF":
            assert with_exemplars, "EOF marker outside openmetrics mode"
            continue
        if line.startswith("# TYPE "):
            _, _, family, kind = line.split(" ", 3)
            assert kind in ("counter", "gauge", "histogram"), line
            types[family] = kind
            continue
        assert not line.startswith("#"), f"unknown comment {line!r}"
        if " # " in line:
            assert with_exemplars, f"exemplar in plain exposition: {line!r}"
            line, ex = line.split(" # ", 1)
            assert ex.startswith("{"), f"malformed exemplar {ex!r}"
            labelpart, _, rest = ex[1:].partition("} ")
            ev, _, ets = rest.partition(" ")
            ex_labels = {}
            if labelpart:
                for pair in labelpart.split('",'):
                    k, _, v = pair.partition('="')
                    ex_labels[k] = v.rstrip('"')
            name_for_ex = line.rpartition(" ")[0]
            exemplars[name_for_ex] = {
                "labels": ex_labels,
                "value": float(ev),
                "ts": float(ets),
            }
        name, _, value = line.rpartition(" ")
        assert name, f"malformed sample line {line!r}"
        samples[name] = float(value.replace("+Inf", "inf"))
    if with_exemplars:
        return samples, types, exemplars
    return samples, types


# ---- registry ----


class TestRegistry:
    def test_counter_gauge_basics(self):
        r = MetricsRegistry()
        c = r.counter("wavetpu_t_total", "things")
        c.inc()
        c.inc(2.5)
        assert c.value() == 3.5
        with pytest.raises(ValueError, match="decrease"):
            c.inc(-1)
        g = r.gauge("wavetpu_t_gauge", "level")
        g.set(7)
        g.dec(2)
        assert g.value() == 5

    def test_labels_and_reregistration(self):
        r = MetricsRegistry()
        c = r.counter("wavetpu_l_total", "labeled", ("path",))
        c.inc(path="roll")
        c.inc(3, path="kfused")
        assert c.value(path="roll") == 1
        assert c.value(path="kfused") == 3
        # idempotent re-registration returns the same child
        assert r.counter("wavetpu_l_total", "labeled", ("path",)) is c
        # type or labelname mismatch is a loud error, not a silent fork
        with pytest.raises(ValueError, match="already registered"):
            r.gauge("wavetpu_l_total", "labeled", ("path",))
        with pytest.raises(ValueError, match="already registered"):
            r.counter("wavetpu_l_total", "labeled", ("other",))
        # wrong labels at call time
        with pytest.raises(ValueError, match="wants labels"):
            c.inc(nope="x")

    def test_histogram_buckets_cumulative(self):
        r = MetricsRegistry()
        h = r.histogram("wavetpu_h_seconds", "lat", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        samples, types = parse_prometheus(r.render_prometheus())
        assert types["wavetpu_h_seconds"] == "histogram"
        assert samples['wavetpu_h_seconds_bucket{le="0.1"}'] == 1
        assert samples['wavetpu_h_seconds_bucket{le="1"}'] == 2
        assert samples['wavetpu_h_seconds_bucket{le="+Inf"}'] == 3
        assert samples["wavetpu_h_seconds_count"] == 3
        assert samples["wavetpu_h_seconds_sum"] == pytest.approx(5.55)

    def test_label_escaping(self):
        r = MetricsRegistry()
        c = r.counter("wavetpu_esc_total", "esc", ("src",))
        c.inc(src='a"b\\c\nd')
        text = r.render_prometheus()
        assert 'wavetpu_esc_total{src="a\\"b\\\\c\\nd"} 1' in text
        # the escaped line round-trips through the parser
        samples, _ = parse_prometheus(text)
        assert samples['wavetpu_esc_total{src="a\\"b\\\\c\\nd"}'] == 1

    def test_snapshot_and_text_agree(self):
        r = MetricsRegistry()
        r.counter("wavetpu_a_total", "a").inc(4)
        r.gauge("wavetpu_b", "b").set(2.5)
        snap = r.snapshot()
        samples, _ = parse_prometheus(r.render_prometheus())
        assert snap["wavetpu_a_total"] == samples["wavetpu_a_total"] == 4
        assert snap["wavetpu_b"] == samples["wavetpu_b"] == 2.5

    def test_histogram_exemplars_openmetrics_only(self):
        """Exemplars pin a request id to the bucket an observation
        landed in, render ONLY in the openmetrics view (`# {labels} v
        ts` + `# EOF`), and the classic 0.0.4 text stays byte-stable
        for parsers that do not speak the suffix."""
        r = MetricsRegistry()
        h = r.histogram("wavetpu_ex_seconds", "lat", buckets=(0.1, 1.0))
        h.observe(0.05, exemplar={"request_id": "lg-1"})
        h.observe(0.5)  # no exemplar for this bucket
        h.observe(7.0, exemplar={"request_id": "lg-3"})
        plain = r.render_prometheus()
        assert " # " not in plain and "# EOF" not in plain
        parse_prometheus(plain)  # still valid 0.0.4
        om = r.render_prometheus(openmetrics=True)
        samples, types, exemplars = parse_prometheus(
            om, with_exemplars=True
        )
        assert om.rstrip().endswith("# EOF")
        assert types["wavetpu_ex_seconds"] == "histogram"
        # the 0.05 observation landed in the le=0.1 bucket...
        ex = exemplars['wavetpu_ex_seconds_bucket{le="0.1"}']
        assert ex["labels"] == {"request_id": "lg-1"}
        assert ex["value"] == pytest.approx(0.05)
        assert ex["ts"] > 0
        # ...the 7.0 one overflowed to +Inf...
        assert exemplars['wavetpu_ex_seconds_bucket{le="+Inf"}'][
            "labels"
        ] == {"request_id": "lg-3"}
        # ...and the exemplar-less bucket has none.
        assert 'wavetpu_ex_seconds_bucket{le="1"}' not in exemplars
        # counts are untouched by exemplar bookkeeping
        assert samples["wavetpu_ex_seconds_count"] == 3

    def test_openmetrics_counter_family_drops_total_suffix(self):
        """OpenMetrics names a counter FAMILY without the _total suffix
        (samples keep it); the 0.0.4 view keeps the historical
        full-name TYPE line so existing scrapes are untouched."""
        r = MetricsRegistry()
        r.counter("wavetpu_om_total", "c").inc()
        om = r.render_prometheus(openmetrics=True)
        assert "# TYPE wavetpu_om counter" in om
        assert "\nwavetpu_om_total 1" in om
        plain = r.render_prometheus()
        assert "# TYPE wavetpu_om_total counter" in plain

    def test_exemplar_latest_wins_per_bucket(self):
        r = MetricsRegistry()
        h = r.histogram("wavetpu_ex2_seconds", "lat", buckets=(1.0,))
        h.observe(0.1, exemplar={"request_id": "a"})
        h.observe(0.2, exemplar={"request_id": "b"})
        _, _, exemplars = parse_prometheus(
            r.render_prometheus(openmetrics=True), with_exemplars=True
        )
        assert exemplars['wavetpu_ex2_seconds_bucket{le="1"}'][
            "labels"
        ] == {"request_id": "b"}

    def test_snapshot_is_one_consistent_cut(self):
        # A writer bumps two counters under the registry lock; no
        # snapshot may ever observe them out of step.
        r = MetricsRegistry()
        a = r.counter("wavetpu_pair_a_total", "a")
        b = r.counter("wavetpu_pair_b_total", "b")
        stop = threading.Event()

        def writer():
            while not stop.is_set():
                with r.lock:
                    a.inc()
                    b.inc()

        t = threading.Thread(target=writer, daemon=True)
        t.start()
        try:
            for _ in range(200):
                snap = r.snapshot()
                assert snap["wavetpu_pair_a_total"] == \
                    snap["wavetpu_pair_b_total"]
        finally:
            stop.set()
            t.join()


# ---- tracing ----


class TestTracing:
    def test_disabled_tracer_is_noop(self):
        """Untraced, nothing is recorded: the handle holds only the
        profiler annotation (no span id), and None / a second end are
        no-ops."""
        tracing.disable()
        h = tracing.begin_span("x")
        assert set(h) == {"_annotation"} and not tracing.enabled()
        tracing.end_span(h)
        tracing.end_span(h)
        tracing.end_span(None)
        tracing.event("x", a=1)  # no crash, nothing written
        with tracing.span("x", a=1) as attrs:
            attrs["b"] = 2  # throwaway dict

    def test_spans_nest_and_link(self, tmp_path):
        path = str(tmp_path / "trace.jsonl")
        tracing.configure(path)
        try:
            with tracing.span("outer", who="parent"):
                with tracing.span("inner") as attrs:
                    attrs["found"] = 42
                tracing.event("ping", n=1)
        finally:
            tracing.disable()
        recs = [json.loads(line) for line in open(path)]
        by_kind = {r["kind"]: r for r in recs}
        # inner closes first (JSONL is emission-ordered)
        assert [r["kind"] for r in recs] == ["inner", "ping", "outer"]
        assert by_kind["inner"]["parent_id"] == by_kind["outer"]["span_id"]
        assert by_kind["ping"]["parent_id"] == by_kind["outer"]["span_id"]
        assert by_kind["inner"]["attrs"]["found"] == 42
        assert by_kind["outer"]["attrs"]["who"] == "parent"
        assert by_kind["outer"]["dur_s"] >= by_kind["inner"]["dur_s"]
        assert by_kind["ping"]["type"] == "event"

    def test_parenthood_is_thread_local(self, tmp_path):
        path = str(tmp_path / "trace.jsonl")
        tracing.configure(path)
        try:
            with tracing.span("main-span"):
                done = threading.Event()

                def other():
                    with tracing.span("other-thread"):
                        pass
                    done.set()

                threading.Thread(target=other).start()
                assert done.wait(10)
        finally:
            tracing.disable()
        recs = {r["kind"]: r for r in
                (json.loads(line) for line in open(path))}
        assert recs["other-thread"]["parent_id"] is None

    def test_attr_named_kind_allowed(self, tmp_path):
        path = str(tmp_path / "trace.jsonl")
        tracing.configure(path)
        try:
            tracing.event("checkpoint.save", kind="single", step=3)
        finally:
            tracing.disable()
        (rec,) = [json.loads(line) for line in open(path)]
        assert rec["kind"] == "checkpoint.save"
        assert rec["attrs"]["kind"] == "single"

    def test_end_span_idempotent(self, tmp_path):
        """A crash-path end_span can race the normal end on the same
        handle (supervisor's except handler after a chunk span already
        closed); the second end must be a silent no-op - one record, no
        KeyError masking the original exception, clean parent stack."""
        path = str(tmp_path / "trace.jsonl")
        tracing.configure(path)
        try:
            h = tracing.begin_span("x", a=1)
            tracing.end_span(h, ok=True)
            tracing.end_span(h, error="boom")  # must not raise or emit
            assert tracing.get_tracer().current_span_id() is None
        finally:
            tracing.disable()
        (rec,) = [json.loads(line) for line in open(path)]
        assert rec["attrs"] == {"a": 1, "ok": True}


class TestTraceContext:
    """W3C trace-context plumbing (docs/observability.md "Distributed
    tracing"): traceparent parse/format, remote-parent adoption, trace
    id inheritance and stamping, and cross-trace links."""

    def test_mint_and_roundtrip(self):
        tid, sid = tracing.mint_trace_id(), tracing.mint_span_id()
        assert len(tid) == 32 and len(sid) == 16
        int(tid, 16), int(sid, 16)
        header = tracing.format_traceparent(tid, sid)
        assert tracing.parse_traceparent(header) == (tid, sid)

    def test_parse_rejects_garbage(self):
        bad = [
            None, "", "garbage", "00-abc-def-01",
            "00-" + "g" * 32 + "-" + "1" * 16 + "-01",   # non-hex
            "00-" + "0" * 32 + "-" + "1" * 16 + "-01",   # zero trace
            "00-" + "1" * 32 + "-" + "0" * 16 + "-01",   # zero parent
            "ff-" + "1" * 32 + "-" + "2" * 16 + "-01",   # reserved ver
            "00-" + "1" * 31 + "-" + "2" * 16 + "-01",   # short trace
            "00-" + "1" * 32 + "-" + "2" * 16 + "-01-x",  # 5 fields
        ]
        for header in bad:
            assert tracing.parse_traceparent(header) is None, header

    def test_remote_adoption_and_inheritance(self, tmp_path):
        """A span opened with remote=(tid, wire_parent) records that
        exact parentage, and SAME-THREAD children inherit the trace id
        through the stack."""
        path = str(tmp_path / "trace.jsonl")
        tracing.configure(path)
        tid = tracing.mint_trace_id()
        wire = tracing.mint_span_id()
        try:
            with tracing.span("rx", remote=(tid, wire)):
                with tracing.span("child"):
                    tracing.event("tick")
        finally:
            tracing.disable()
        recs = {r["kind"]: r for r in
                (json.loads(line) for line in open(path))}
        assert recs["rx"]["parent_id"] == wire
        assert recs["rx"]["trace_id"] == tid
        assert recs["child"]["trace_id"] == tid
        assert recs["child"]["parent_id"] == recs["rx"]["span_id"]
        assert recs["tick"]["trace_id"] == tid

    def test_trace_id_stamp_without_parenthood(self, tmp_path):
        """trace_id= alone (the scheduler-thread serve.chunk case)
        stamps the record but leaves it a tree root."""
        path = str(tmp_path / "trace.jsonl")
        tracing.configure(path)
        tid = tracing.mint_trace_id()
        try:
            with tracing.span("chunk", trace_id=tid):
                pass
        finally:
            tracing.disable()
        (rec,) = [json.loads(line) for line in open(path)]
        assert rec["trace_id"] == tid and rec["parent_id"] is None

    def test_links_recorded(self, tmp_path):
        path = str(tmp_path / "trace.jsonl")
        tracing.configure(path)
        link = {"trace_id": "ab" * 16, "span_id": "cd" * 8}
        try:
            with tracing.span("resumed", links=[link]):
                pass
            with tracing.span("plain"):
                pass
        finally:
            tracing.disable()
        recs = {r["kind"]: r for r in
                (json.loads(line) for line in open(path))}
        assert recs["resumed"]["links"] == [link]
        assert "links" not in recs["plain"]

    def test_untraced_records_carry_no_trace_id(self, tmp_path):
        path = str(tmp_path / "trace.jsonl")
        tracing.configure(path)
        try:
            with tracing.span("solo"):
                pass
        finally:
            tracing.disable()
        (rec,) = [json.loads(line) for line in open(path)]
        assert "trace_id" not in rec


class TestTraceJoiner:
    """The cross-process joiner: wire-id resolution, multi-source
    merge, link-following trace closure, and the --dir CLI."""

    @staticmethod
    def _two_tier_trace(tmp_path):
        """A router + replica trace pair for one request 'r-1', plus an
        unrelated request on the replica."""
        tid = tracing.mint_trace_id()
        att_w3c = tracing.mint_span_id()
        router = tmp_path / "router"
        replica = tmp_path / "replica"
        tr = tracing.Tracer(str(router / "trace.jsonl"))
        h = tr.begin("router.request",
                     {"request_id": "r-1", "w3c_id": "aa" * 8},
                     remote=(tid, None))
        ha = tr.begin("router.attempt",
                      {"request_id": "r-1", "w3c_id": att_w3c})
        tr.end(ha, status=200)
        tr.end(h, status=200)
        tr.close()
        t2 = tracing.Tracer(str(replica / "trace.jsonl"))
        t2._prefix = "fffe"  # simulate a second process
        h2 = t2.begin("serve.request",
                      {"request_id": "r-1", "w3c_id": "bb" * 8},
                      remote=(tid, att_w3c))
        t2.end(h2, status=200)
        h3 = t2.begin("serve.request", {"request_id": "r-2"},
                      remote=(tracing.mint_trace_id(), None))
        t2.end(h3, status=200)
        t2.close()
        return str(router), str(replica), tid

    def test_join_resolves_wire_parent(self, tmp_path):
        router, replica, tid = self._two_tier_trace(tmp_path)
        records = obs_report.load_traces([
            os.path.join(router, "trace.jsonl"),
            os.path.join(replica, "trace.jsonl"),
        ])
        joined = obs_report.join_processes(records)
        by_kind = {r["kind"]: r for r in joined
                   if r["attrs"].get("request_id") == "r-1"}
        assert (by_kind["serve.request"]["parent_id"]
                == by_kind["router.attempt"]["span_id"])
        view = obs_report.request_view(records, "r-1")
        kinds = [r["kind"] for r in view]
        assert kinds == ["router.request", "router.attempt",
                         "serve.request"]
        assert {r["trace_id"] for r in view} == {tid}
        text = obs_report.format_request_view(view, "r-1")
        assert "joined across 2 processes" in text
        assert "<-hop" in text

    def test_unresolvable_wire_parent_roots_cleanly(self, tmp_path):
        """A replica-only view (upstream dir not passed) must render the
        serve.request as a root, not dangle under an unknown parent."""
        _, replica, _ = self._two_tier_trace(tmp_path)
        records = obs_report.load_trace(
            os.path.join(replica, "trace.jsonl"))
        view = obs_report.request_view(records, "r-1")
        assert [r["kind"] for r in view] == ["serve.request"]
        assert view[0]["parent_id"] is None

    def test_link_closure_joins_resume_chain_both_ways(self, tmp_path):
        """A march resumed under a FRESH trace links back to the
        originating request; querying by EITHER request id must pull in
        the whole chain."""
        t = tracing.Tracer(str(tmp_path / "trace.jsonl"))
        tid1, tid2 = tracing.mint_trace_id(), tracing.mint_trace_id()
        h = t.begin("serve.request", {"request_id": "orig"},
                    remote=(tid1, None))
        origin = [tid1, "ee" * 8]
        t.end(h, status=504)
        h2 = t.begin("serve.request", {"request_id": "resumed"},
                     remote=(tid2, None))
        t.end(h2, status=200)
        hc = t.begin(
            "serve.chunk", {"request_id": "resumed"}, trace_id=tid2,
            links=[{"trace_id": origin[0], "span_id": origin[1]}],
        )
        t.end(hc)
        t.close()
        records = obs_report.load_trace(str(tmp_path / "trace.jsonl"))
        for rid in ("orig", "resumed"):
            view = obs_report.request_view(records, rid)
            kinds = sorted(r["kind"] for r in view)
            assert kinds == ["serve.chunk", "serve.request",
                             "serve.request"], (rid, kinds)
        text = obs_report.format_request_view(
            obs_report.request_view(records, "orig"), "orig")
        assert "~>resumed-from" in text

    def test_cli_multi_dir(self, tmp_path, capsys):
        from wavetpu.cli import main

        router, replica, _ = self._two_tier_trace(tmp_path)
        assert main(["trace-report", "--dir", router, "--dir", replica,
                     "--request", "r-1"]) == 0
        out = capsys.readouterr().out
        assert "router.attempt" in out and "serve.request" in out
        # summary mode merges too
        assert main(["trace-report", "--dir", router,
                     "--dir", replica]) == 0
        out = capsys.readouterr().out
        assert "router.request" in out and "serve.request" in out
        # no sources is a usage error
        assert main(["trace-report"]) == 2

    def test_multi_source_merge_includes_rotated(self, tmp_path):
        """--dir merges each source's rotated segment set oldest-first
        (the long-lived-server case)."""
        a = tmp_path / "a"
        tracing.configure(str(a / "trace.jsonl"), max_bytes=300, keep=3)
        try:
            for i in range(12):
                tracing.event("rot.tick", n=i)
        finally:
            tracing.disable()
        b = tmp_path / "b"
        tracing.configure(str(b / "trace.jsonl"))
        try:
            tracing.event("other.tick", n=99)
        finally:
            tracing.disable()
        records = obs_report.load_traces([
            str(a / "trace.jsonl"), str(b / "trace.jsonl"),
        ])
        kinds = {r["kind"] for r in records}
        assert kinds == {"rot.tick", "other.tick"}
        ns = [r["attrs"]["n"] for r in records
              if r["kind"] == "rot.tick"]
        assert ns == sorted(ns) and ns[-1] == 11 and len(ns) > 1


class TestMetricCatalogLint:
    """Every wavetpu_* metric the code constructs must be documented in
    docs/observability.md's metric catalog - an undocumented metric is
    a tier-1 failure, not a drive-by (ISSUE: the catalog is the
    contract operators alert on)."""

    @staticmethod
    def _constructed_metrics():
        import re

        root = os.path.join(os.path.dirname(__file__), "..", "wavetpu")
        ctor = re.compile(
            r"(?:counter|gauge|histogram)\(\s*['\"]"
            r"(wavetpu_[a-z0-9_]+)['\"]"
        )
        # The router renders its own samples as text, not through the
        # registry - catch every full-name literal there too.  The
        # control-plane store and HA coordinator do the same with
        # wavetpu_store_* / wavetpu_fleet_* samples.
        literal_res = {
            "router.py": re.compile(
                r"['\"](wavetpu_router_[a-z0-9_]+)"
            ),
            "store.py": re.compile(
                r"['\"](wavetpu_store_[a-z0-9_]+)"
            ),
            "ha.py": re.compile(
                r"['\"](wavetpu_fleet_[a-z0-9_]+)"
            ),
        }
        names = set()
        for dirpath, _dirs, files in os.walk(root):
            if "__pycache__" in dirpath:
                continue
            for fn in files:
                if not fn.endswith(".py"):
                    continue
                src = open(os.path.join(dirpath, fn),
                           encoding="utf-8").read()
                names.update(ctor.findall(src))
                lit = literal_res.get(fn)
                if lit is not None:
                    names.update(
                        m for m in lit.findall(src)
                        if not m.endswith("_")
                    )
        return names

    def test_every_constructed_metric_is_documented(self):
        import re

        doc = open(
            os.path.join(os.path.dirname(__file__), "..", "docs",
                         "observability.md"),
            encoding="utf-8",
        ).read()
        documented = set(re.findall(r"wavetpu_[a-z0-9_]+", doc))
        constructed = self._constructed_metrics()
        assert constructed, "lint found no metrics - pattern broke?"
        missing = sorted(constructed - documented)
        assert not missing, (
            f"metrics constructed in wavetpu/ but absent from "
            f"docs/observability.md's catalog: {missing}"
        )


class TestTraceRotation:
    """Size-based telemetry rotation: a long-lived server must not grow
    trace.jsonl / heartbeat.jsonl forever (keep-last-K segments, atomic
    os.replace shifts), and trace-report reads the whole rotated set."""

    def test_tracer_rotates_and_keeps_k_segments(self, tmp_path):
        path = str(tmp_path / "trace.jsonl")
        # ~120 B records against a 400 B cap: every few events rotate.
        tracing.configure(path, max_bytes=400, keep=3)
        try:
            for i in range(40):
                tracing.event("rot.tick", n=i)
        finally:
            tracing.disable()
        segs = [p.name for p in sorted(tmp_path.iterdir())]
        assert "trace.jsonl" in segs
        assert "trace.jsonl.1" in segs and "trace.jsonl.2" in segs
        assert "trace.jsonl.3" not in segs  # keep=3 total segments
        for p in tmp_path.iterdir():
            assert p.stat().st_size <= 400 + 200  # cap + one record slack

    def test_load_trace_reads_rotated_set_oldest_first(self, tmp_path):
        path = str(tmp_path / "trace.jsonl")
        tracing.configure(path, max_bytes=400, keep=4)
        try:
            for i in range(30):
                tracing.event("rot.tick", n=i)
        finally:
            tracing.disable()
        records = obs_report.load_trace(path)
        ns = [r["attrs"]["n"] for r in records]
        # the retained window is contiguous, ordered, and ends at the
        # newest record; older-than-window records were GCed
        assert ns == list(range(ns[0], 30))
        # include_rotated=False reads only the live segment
        live = obs_report.load_trace(path, include_rotated=False)
        assert len(live) < len(records)
        # segments enumerate oldest -> newest, live file last
        segs = obs_report.trace_segments(path)
        assert segs[-1] == path and len(segs) >= 2

    def test_heartbeat_rotation(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("wavetpu_beats_total", "x").inc()
        tel = telemetry.start(str(tmp_path), registry=reg,
                              interval=60.0, max_bytes=300, keep=2)
        try:
            for _ in range(20):
                tel.beat()
        finally:
            tel.stop()
        assert (tmp_path / "heartbeat.jsonl").exists()
        assert (tmp_path / "heartbeat.jsonl.1").exists()
        assert not (tmp_path / "heartbeat.jsonl.2").exists()
        # every retained line is whole JSON (atomic rotation, no tears)
        for name in ("heartbeat.jsonl", "heartbeat.jsonl.1"):
            for line in open(tmp_path / name):
                assert "metrics" in json.loads(line)

    def test_rotation_disabled_by_default_for_direct_configure(
        self, tmp_path
    ):
        path = str(tmp_path / "trace.jsonl")
        tracing.configure(path)
        try:
            for i in range(50):
                tracing.event("rot.tick", n=i)
        finally:
            tracing.disable()
        assert not (tmp_path / "trace.jsonl.1").exists()
        assert len(obs_report.load_trace(path)) == 50


# ---- trace-report ----


def _synthetic_trace(tmp_path):
    recs = [
        {"type": "span", "kind": "serve.request", "span_id": "p-1",
         "parent_id": None, "t_start": 10.0, "dur_s": 0.50,
         "attrs": {"request_id": "p-9", "status": 200}},
        {"type": "span", "kind": "serve.execute", "span_id": "p-3",
         "parent_id": "p-2", "t_start": 10.1, "dur_s": 0.30,
         "attrs": {"warm": True}},
        {"type": "span", "kind": "serve.batch", "span_id": "p-2",
         "parent_id": None, "t_start": 10.05, "dur_s": 0.40,
         "attrs": {"request_ids": ["p-9"], "occupancy": 2}},
        {"type": "span", "kind": "serve.request", "span_id": "p-4",
         "parent_id": None, "t_start": 11.0, "dur_s": 0.10,
         "attrs": {"request_id": "p-8", "status": 400}},
        {"type": "event", "kind": "supervisor.retry", "span_id": "p-5",
         "parent_id": None, "t_start": 12.0, "attrs": {"step": 4}},
    ]
    path = tmp_path / "t.jsonl"
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
        f.write("not json\n")  # mid-write tail must not be fatal
    return str(path)


class TestTraceReport:
    def test_summarize(self, tmp_path):
        records = obs_report.load_trace(_synthetic_trace(tmp_path))
        s = obs_report.summarize(records)
        assert s["spans"]["serve.request"]["count"] == 2
        assert s["spans"]["serve.request"]["total_s"] == pytest.approx(0.6)
        assert s["spans"]["serve.request"]["p95_ms"] == pytest.approx(500.0)
        assert s["events"] == {"supervisor.retry": 1}
        text = obs_report.format_summary(s)
        assert "serve.request" in text and "p95_ms" in text

    def test_request_view_joins_batch_and_descendants(self, tmp_path):
        records = obs_report.load_trace(_synthetic_trace(tmp_path))
        view = obs_report.request_view(records, "p-9")
        kinds = [r["kind"] for r in view]
        # the request span, the batch tagged with its id, AND the
        # batch's untagged execute child - the other request excluded
        assert kinds == ["serve.request", "serve.batch", "serve.execute"]
        text = obs_report.format_request_view(view, "p-9")
        assert "serve.execute" in text

    def test_cli_subcommand(self, tmp_path, capsys):
        from wavetpu.cli import main

        path = _synthetic_trace(tmp_path)
        assert main(["trace-report", path]) == 0
        out = capsys.readouterr().out
        assert "serve.batch" in out
        assert main(["trace-report", path, "--request", "p-9"]) == 0
        assert "critical path of request p-9" in capsys.readouterr().out
        assert main(["trace-report"]) == 2
        assert main(["trace-report", str(tmp_path / "missing.jsonl")]) == 2


# ---- telemetry dir ----


class TestTelemetry:
    def test_heartbeat_and_prom_files(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("wavetpu_beats_total", "x").inc(5)
        tel = telemetry.start(str(tmp_path), registry=reg, interval=60.0)
        try:
            tracing.event("hello", n=1)
        finally:
            tel.stop()
        beats = [json.loads(line)
                 for line in open(tmp_path / "heartbeat.jsonl")]
        assert beats  # stop() always writes a final beat
        assert beats[-1]["metrics"]["wavetpu_beats_total"] == 5
        samples, _ = parse_prometheus(open(tmp_path / "metrics.prom").read())
        assert samples["wavetpu_beats_total"] == 5
        recs = [json.loads(line) for line in open(tmp_path / "trace.jsonl")]
        assert recs[0]["kind"] == "hello"
        # tracer is torn down with the handle
        assert not tracing.enabled()


# ---- solver counters ----


class TestSolveCounters:
    def test_leapfrog_solve_increments_registry(self, small_problem):
        from wavetpu.solver import leapfrog

        reg = get_registry()
        c = reg.counter("wavetpu_solves_total",
                        "completed solve entry points", ("path",))
        before = c.value(path="leapfrog")
        cells = reg.counter(
            "wavetpu_solve_cells_total",
            "cell updates marched ((N+1)^3 per layer)", ("path",),
        )
        cells_before = cells.value(path="leapfrog")
        leapfrog.solve(small_problem)
        assert c.value(path="leapfrog") == before + 1
        expected = (
            small_problem.cells_per_step * small_problem.timesteps
        )
        assert cells.value(path="leapfrog") - cells_before == \
            pytest.approx(expected)


# ---- acceptance: supervised multi-chunk run under --telemetry-dir ----


class TestSupervisedTelemetry:
    def test_chunk_spans_match_checkpoint_rotation(self, tmp_path):
        """The ISSUE's acceptance drill: a supervised multi-chunk run
        with telemetry on emits chunk spans whose boundaries equal the
        checkpoint steps (spans AND rotation entries on disk), and
        trace-report summarizes them."""
        from wavetpu.cli import main
        from wavetpu.run.supervisor import _entry_step

        tel = tmp_path / "tel"
        ckpt = tmp_path / "ckpt"
        rc = main([
            "16", "1", "1", "1", "1", "1", "12", "--backend", "single",
            "--ckpt-every", "4", "--ckpt-dir", str(ckpt),
            "--telemetry-dir", str(tel), "--out-dir", str(tmp_path),
        ])
        assert rc == 0
        recs = [json.loads(line) for line in open(tel / "trace.jsonl")]
        chunk_ends = sorted(
            r["attrs"]["end"] for r in recs
            if r["kind"] == "supervisor.chunk"
        )
        ckpt_steps = sorted(
            r["attrs"]["step"] for r in recs
            if r["kind"] == "supervisor.checkpoint"
        )
        # ckpt_every=4 over 12 layers: first chunk marches 1+4, then 4+3
        assert chunk_ends == [5, 9, 12]
        assert ckpt_steps == chunk_ends
        # ...and the spans agree with the rotation on disk (keep-2 GC
        # leaves the newest two entries).
        disk_steps = sorted(
            s for e in os.listdir(ckpt)
            if (s := _entry_step(e)) is not None
        )
        assert disk_steps == ckpt_steps[-2:]
        # every chunk span nests under the one supervisor.march span
        march = [r for r in recs if r["kind"] == "supervisor.march"]
        assert len(march) == 1
        assert march[0]["attrs"]["status"] == "complete"
        for r in recs:
            if r["kind"] == "supervisor.chunk":
                assert r["parent_id"] == march[0]["span_id"]
        # io-layer events carry byte counts
        saves = [r for r in recs if r["kind"] == "checkpoint.save"]
        assert saves and all(r["attrs"]["bytes"] > 0 for r in saves)
        # heartbeat carries the supervisor counters
        beats = [json.loads(line) for line in open(tel / "heartbeat.jsonl")]
        assert beats[-1]["metrics"]["wavetpu_supervisor_checkpoints_total"] \
            >= 3
        # and trace-report summarizes the trace
        s = obs_report.summarize(recs)
        assert s["spans"]["supervisor.chunk"]["count"] == 3
        assert "supervisor.checkpoint" in s["spans"]
        assert not tracing.enabled()  # CLI tore telemetry down

    def test_seed_checkpoint_counted(self, small_problem, tmp_path):
        """An injected-state resume into an empty rotation root seeds
        the rotation with the caller's checkpoint; the registry counter
        must count that entry like SupervisedResult.checkpoints_written
        (else the counters-vs-rotation audit reports a false mismatch)."""
        from wavetpu.io import checkpoint
        from wavetpu.run import supervisor as sup

        c = get_registry().counter(
            "wavetpu_supervisor_checkpoints_total",
            "rotation entries written",
        )
        r = sup.supervise(
            small_problem, sup.PathSpec(),
            sup.SupervisorOptions(ckpt_every=3,
                                  ckpt_dir=str(tmp_path / "rot")),
        )
        _, u_prev, u_cur, step = checkpoint.load_checkpoint(
            r.checkpoint_path
        )
        before = c.value()
        r2 = sup.supervise(
            small_problem, sup.PathSpec(),
            sup.SupervisorOptions(ckpt_every=3,
                                  ckpt_dir=str(tmp_path / "rot2")),
            state=(u_prev, u_cur), start_step=step,
        )
        assert r2.checkpoints_written >= 2  # the seed + the final save
        assert c.value() - before == r2.checkpoints_written

    def test_crash_mid_dispatch_stops_telemetry(self, tmp_path,
                                                monkeypatch):
        """An exception inside the solve dispatch must still emit the
        open cli.solve span, stop the heartbeat daemon, and unbind the
        process tracer - in-process callers (this test) never reach the
        atexit net, and a later run must not inherit a stale tracer."""
        from wavetpu.cli import main
        from wavetpu.solver import leapfrog

        def boom(*a, **kw):
            raise RuntimeError("injected mid-dispatch failure")

        monkeypatch.setattr(leapfrog, "solve", boom)
        tel = tmp_path / "tel"
        with pytest.raises(RuntimeError, match="injected"):
            main([
                "16", "1", "1", "1", "1", "1", "10", "--backend",
                "single", "--kernel", "roll", "--telemetry-dir",
                str(tel), "--out-dir", str(tmp_path),
            ])
        assert not tracing.enabled()
        recs = [json.loads(line) for line in open(tel / "trace.jsonl")]
        (span,) = [r for r in recs if r["kind"] == "cli.solve"]
        assert span["attrs"]["aborted"] is True
        # the final heartbeat landed too
        assert (tel / "heartbeat.jsonl").exists()


# ---- program spans in the profiler's trace ----


class _Annotations:
    """Stands in for jax.profiler.TraceAnnotation: records each enter
    and exit by name, so a test can see spans open and close."""

    def __init__(self):
        self.events = []
        rec = self.events

        class Annotation:
            def __init__(self, name, **kwargs):
                self.name = name

            def __enter__(self):
                rec.append(("enter", self.name))
                return self

            def __exit__(self, *exc):
                rec.append(("exit", self.name))

        self.cls = Annotation

    def balanced(self):
        opened = [n for e, n in self.events if e == "enter"]
        closed = [n for e, n in self.events if e == "exit"]
        return sorted(opened) == sorted(closed)

    def kinds(self):
        return {n for e, n in self.events if e == "enter"}


@pytest.fixture()
def annotations(monkeypatch):
    import jax

    a = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", a.cls)
    return a


class TestProgramSpans:
    """Every span is a profiler annotation whether or not a tracer is
    configured; the JSONL record is written only under a tracer."""

    def test_untraced_spans_open_one_annotation(self, annotations,
                                                tmp_path, monkeypatch):
        tracing.disable()
        monkeypatch.chdir(tmp_path)
        with tracing.span("a.span", x=1) as attrs:
            attrs["y"] = 2
        assert annotations.events == [("enter", "a.span"),
                                      ("exit", "a.span")]
        h = tracing.begin_span("b.span", remote=("1" * 32, None))
        assert annotations.events[-1] == ("enter", "b.span")
        tracing.end_span(h, status=200)
        tracing.end_span(h, status=500)  # a second end closes nothing
        assert annotations.events[-2:] == [("enter", "b.span"),
                                           ("exit", "b.span")]
        assert os.listdir(tmp_path) == []  # no file written

    def test_annotation_closes_on_exception(self, annotations):
        tracing.disable()
        with pytest.raises(RuntimeError):
            with tracing.span("boom.span"):
                raise RuntimeError("inside")
        assert annotations.events == [("enter", "boom.span"),
                                      ("exit", "boom.span")]

    def test_traced_span_is_one_annotation_and_one_record(
            self, annotations, tmp_path):
        path = str(tmp_path / "trace.jsonl")
        tracing.configure(path)
        try:
            with tracing.span("c.span"):
                h = tracing.begin_span("d.span")
                tracing.end_span(h, ok=True)
        finally:
            tracing.disable()
        assert annotations.events == [
            ("enter", "c.span"), ("enter", "d.span"),
            ("exit", "d.span"), ("exit", "c.span")]
        assert [json.loads(line)["kind"] for line in open(path)] == [
            "d.span", "c.span"]

    def test_tracer_configured_mid_span_still_closes(self, annotations,
                                                     tmp_path):
        """A light handle begun untraced is closed, not recorded, by an
        end that finds a tracer configured since (and the reverse)."""
        tracing.disable()
        h = tracing.begin_span("e.span")
        path = str(tmp_path / "trace.jsonl")
        tracing.configure(path)
        try:
            tracing.end_span(h)
            h2 = tracing.begin_span("f.span")
        finally:
            tracing.disable()
        tracing.end_span(h2)
        assert annotations.balanced()
        assert open(path).read() == ""

    def test_untraced_cli_solve_keeps_its_behaviour(self, annotations,
                                                    tmp_path):
        """The solo CLI untraced: no trace file, no telemetry thread,
        the solve's program spans open and close as annotations, and
        the traced-only roofline lookup (`tracing.enabled()`) is
        skipped."""
        from wavetpu.cli import main

        tracing.disable()
        before = set(threading.enumerate())
        rc = main(["8", "1", "1", "1", "1", "1", "4", "--backend",
                   "single", "--kernel", "roll", "--out-dir",
                   str(tmp_path)])
        assert rc == 0
        assert annotations.balanced()
        assert {"cli.solve", "solve.prepare", "solve.run",
                "solve.finish"} <= annotations.kinds()
        assert not any(p.endswith(".jsonl") for p in os.listdir(tmp_path))
        assert set(threading.enumerate()) <= before  # no thread left


def _host_spans(reduced, kind):
    return sorted((s for s in reduced.spans if s.name.split("#")[0] == kind),
                  key=lambda s: s.start)


class TestProfilerCapture:
    """A CPU profiler capture of one solo solve and one served request,
    with no tracer configured, holds the program's spans on its host
    plane, read back by the benchmark's own trace reduction."""

    def test_spans_reach_the_profile(self, tmp_path):
        import sys
        import urllib.request

        import jax
        from jax.profiler import ProfileData

        from wavetpu.core.problem import Problem
        from wavetpu.serve.api import build_server
        from wavetpu.solver import kfused_comp

        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "benchmark"))
        import tracereduce

        tracing.disable()
        httpd, state = build_server(port=0, max_wait=0.01,
                                    default_kernel="roll", interpret=True)
        thread = threading.Thread(target=httpd.serve_forever, daemon=True)
        thread.start()
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        problem = Problem(N=8, Np=1, Lx=1.0, Ly=1.0, Lz=1.0, T=1.0,
                          timesteps=9)
        body = json.dumps({"N": 8, "timesteps": 4}).encode()

        def solve_and_serve():
            with jax.profiler.TraceAnnotation("bench.solve"):
                kfused_comp.solve_kfused_comp(problem, k=4, interpret=True)
            req = urllib.request.Request(
                base + "/solve", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=300) as r:
                assert r.status == 200

        out = str(tmp_path / "prof")
        try:
            # Warm: the first call compiles the entry's eager constants
            # while it builds its runner, before `solve.prepare` opens.
            solve_and_serve()
            jax.profiler.start_trace(out)
            try:
                with jax.profiler.TraceAnnotation("bench.window"):
                    solve_and_serve()
            finally:
                jax.profiler.stop_trace()
        finally:
            httpd.shutdown()
            state.batcher.close()
            httpd.server_close()
        assert not os.path.exists(tmp_path / "trace.jsonl")
        red = tracereduce.reduce_profile(
            ProfileData.from_file(tracereduce.xplane_path(out)))

        # solo: prepare, run, finish, once each, tiling the call
        (call,) = _host_spans(red, "bench.solve")
        (prep,) = _host_spans(red, "solve.prepare")
        (run,) = _host_spans(red, "solve.run")
        (fin,) = _host_spans(red, "solve.finish")
        assert call.start <= prep.start < prep.end <= run.start
        assert run.start < run.end <= fin.start < fin.end <= call.end
        gaps = (run.start - prep.end) + (fin.start - run.end)
        assert gaps < 0.05
        covered = (prep.end - prep.start) + (run.end - run.start) \
            + (fin.end - fin.start)
        assert covered >= 0.9 * (call.end - call.start)

        # serve: request > batch > execute > pack, run, results
        (request,) = _host_spans(red, "serve.request")
        (batch,) = _host_spans(red, "serve.batch")
        (execute,) = _host_spans(red, "serve.execute")
        assert request.start <= batch.start < batch.end <= request.end
        assert batch.start <= execute.start < execute.end <= batch.end
        last = execute.start
        for kind in ("ensemble.pack", "ensemble.run", "ensemble.results"):
            (s,) = _host_spans(red, kind)
            assert last <= s.start < s.end <= execute.end, kind
            last = s.end
