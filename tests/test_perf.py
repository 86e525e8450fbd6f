"""Performance attribution ledger + incident flight recorder (ISSUE 6):
wall-time decomposition, padding waste, MFU, the compile ledger, the
/perf endpoint and perf_* gauges, event-triggered debug bundles (fake
clocks, no sleeps), and the new PERF_*/FLIGHT_* config knobs."""

import importlib.util
import json
import os

import pytest
from aiohttp.test_utils import TestClient, TestServer

from fasttalk_tpu.models import get_model_config
from fasttalk_tpu.observability.events import EventLog
from fasttalk_tpu.observability.flight import (FlightRecorder, get_flight,
                                               redact_config)
from fasttalk_tpu.observability.perf import PerfLedger, get_perf
from fasttalk_tpu.observability.trace import Tracer, get_tracer
from fasttalk_tpu.utils.metrics import get_metrics

_SPEC = importlib.util.spec_from_file_location(
    "check_prometheus",
    os.path.join(os.path.dirname(__file__), "..", "scripts",
                 "check_prometheus.py"))
check_prometheus = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(check_prometheus)

_TR_SPEC = importlib.util.spec_from_file_location(
    "trace_report",
    os.path.join(os.path.dirname(__file__), "..", "scripts",
                 "trace_report.py"))
trace_report = importlib.util.module_from_spec(_TR_SPEC)
_TR_SPEC.loader.exec_module(trace_report)

TINY = get_model_config("test-tiny")


def _ledger(tracer, **kw):
    kw.setdefault("window_s", 60.0)
    kw.setdefault("idle_gap_ms", 250.0)
    kw.setdefault("peak_tflops", 0.0)
    return PerfLedger(tracer=tracer, **kw)


def _step(tr, t0, t1, *, tokens=16, rows=32, occupancy=0.5, steps=8,
          slots=4, kv_len=512, flops=0.0, kind="plain"):
    tr.step("engine_step", t0, t1, steps=steps, batch=2, slots=slots,
            occupancy=occupancy, kind=kind, tokens=tokens, rows=rows,
            kv_len=kv_len, flops=flops)


class TestPerfLedger:
    def test_decomposition_sums_to_window(self):
        tr = Tracer(enabled=True)
        # busy [100,101] + [101.1,102.1] + [103,104]: 0.1s short gap
        # (host) and 0.9s long gap (idle, > 250 ms threshold).
        _step(tr, 100.0, 101.0)
        _step(tr, 101.1, 102.1)
        _step(tr, 103.0, 104.0)
        rep = _ledger(tr).report(now=104.0)
        wall = rep["wall"]
        assert wall["window_s"] == pytest.approx(4.0)
        assert wall["device_busy_s"] == pytest.approx(3.0)
        assert wall["host_gap_s"] == pytest.approx(0.1)
        assert wall["idle_s"] == pytest.approx(0.9)
        assert wall["device_busy_frac"] + wall["host_gap_frac"] \
            + wall["idle_frac"] == pytest.approx(1.0, abs=1e-3)

    def test_overlapping_pipeline_calls_merge(self):
        tr = Tracer(enabled=True)
        # Pipelined calls overlap (call N+1 dispatched before N
        # retires): the union must not double-count.
        _step(tr, 100.0, 101.0)
        _step(tr, 100.5, 101.5)
        rep = _ledger(tr).report(now=101.5)
        assert rep["wall"]["device_busy_s"] == pytest.approx(1.5)
        assert rep["wall"]["idle_s"] == pytest.approx(0.0)

    def test_trailing_gap_classified(self):
        tr = Tracer(enabled=True)
        _step(tr, 100.0, 101.0)
        rep = _ledger(tr).report(now=102.0)  # 1s silent tail -> idle
        assert rep["wall"]["idle_s"] == pytest.approx(1.0)
        rep = _ledger(tr).report(now=101.1)  # 0.1s tail -> host gap
        assert rep["wall"]["host_gap_s"] == pytest.approx(0.1)

    def test_padding_waste_and_occupancy(self):
        tr = Tracer(enabled=True)
        # Decode: 32 rows computed, 16 useful. Prefill: 64-row bucket,
        # 40 real prompt tokens. waste = 1 - 56/96.
        _step(tr, 100.0, 101.0, tokens=16, rows=32, occupancy=0.5)
        tr.step("engine_prefill", 101.0, 101.2, bucket=64, tokens=40,
                rows=64, kind="batched")
        rep = _ledger(tr).report(now=101.2)
        toks = rep["tokens"]
        assert toks["decode_tokens"] == 16
        assert toks["prefill_tokens"] == 40
        assert toks["computed_token_rows"] == 96
        assert toks["padding_waste_frac"] == pytest.approx(1 - 56 / 96,
                                                           abs=1e-3)
        assert toks["occupancy_mean"] == pytest.approx(0.5)
        assert toks["useful_tok_s"] == pytest.approx(56 / 1.2, rel=1e-3)
        assert rep["n_decode_calls"] == 1
        assert rep["n_prefill_calls"] == 1

    def test_mfu_against_override_roofline(self):
        tr = Tracer(enabled=True)
        _step(tr, 100.0, 101.0, flops=5e11)
        _step(tr, 101.0, 102.0, flops=5e11)
        rep = _ledger(tr, peak_tflops=1.0).report(now=102.0)
        # 1e12 FLOPs over 2 s = 0.5 TFLOP/s against a 1 TFLOP/s peak.
        assert rep["mfu"]["achieved_tflops"] == pytest.approx(0.5)
        assert rep["mfu"]["mfu"] == pytest.approx(0.5)
        # Unknown roofline (CPU): mfu is null, never a made-up number.
        rep = _ledger(tr, peak_tflops=0.0).report(now=102.0)
        assert rep["mfu"]["mfu"] is None

    def test_empty_report(self):
        rep = _ledger(Tracer(enabled=True)).report(now=100.0)
        assert rep["wall"] is None
        assert rep["tokens"] is None
        assert rep["n_decode_calls"] == 0

    def test_window_excludes_old_records(self):
        tr = Tracer(enabled=True)
        _step(tr, 10.0, 11.0)     # far outside the 60 s window
        _step(tr, 100.0, 101.0)
        rep = _ledger(tr).report(now=101.0)
        assert rep["n_decode_calls"] == 1
        assert rep["wall"]["window_s"] == pytest.approx(1.0)

    def test_model_binding_and_call_flops(self):
        led = _ledger(Tracer(enabled=True))
        assert led.call_flops(10, 512) == 0.0  # unbound
        led.bind_model(TINY, num_slots=4, dtype="bfloat16")
        expect = 10 * (2.0 * TINY.param_count()
                       + 4.0 * TINY.num_layers * TINY.q_dim * 512)
        assert led.call_flops(10, 512) == pytest.approx(expect)

    def test_compile_ledger(self):
        led = _ledger(Tracer(enabled=True))
        led.note_compile("decode", serving=False, kv_len=512, steps=8)
        led.note_compile("decode", serving=True, kv_len=512, steps=8)
        led.note_compile("prefill", serving=False, bucket=64)
        rep = led.report(now=100.0)
        assert rep["compiles"]["total"] == 3
        assert rep["compiles"]["serving"] == 1
        by_key = {e["key"]: e for e in rep["compiles"]["by_key"]}
        assert by_key["decode kv_len=512 steps=8"]["count"] == 2
        led.clear()
        assert led.report(now=100.0)["compiles"]["total"] == 0

    def test_summary_digest(self):
        tr = Tracer(enabled=True)
        _step(tr, 100.0, 101.0)
        s = _ledger(tr).summary(now=101.0)
        assert s["device_busy_frac"] == pytest.approx(1.0)
        assert set(s) >= {"padding_waste_frac", "useful_tok_s", "mfu",
                          "occupancy_mean", "serving_compiles",
                          "attention_kernel", "ceiling_tok_s",
                          "frac_of_ceiling"}

    def test_ceiling_section_and_kernel_binding(self, monkeypatch):
        # docs/ROOFLINE.md: ceiling_tok_s = peak_hbm / bytes-per-token,
        # and frac_of_ceiling must equal hbm.bw_util by construction.
        monkeypatch.setenv("PERF_PEAK_HBM_GBPS", "100.0")
        tr = Tracer(enabled=True)
        for t0 in (100.0, 101.0):
            tr.step("engine_step", t0, t0 + 1.0, steps=8, batch=2,
                    slots=4, occupancy=1.0, kind="plain", tokens=16,
                    rows=16, kv_len=512, flops=0.0,
                    kv_bytes=20e9, weight_bytes=5e9)
        led = _ledger(tr)
        led.bind_model(TINY, num_slots=4, dtype="bfloat16",
                       attention_kernel="pallas_dense")
        rep = led.report(now=102.0)
        assert rep["model"]["attention_kernel"] == "pallas_dense"
        # 50 GB over 2 s against a 100 GB/s peak; 32 useful tokens.
        assert rep["hbm"]["bw_util"] == pytest.approx(0.25)
        ceil = rep["ceiling"]
        assert ceil["hbm_bytes_per_token"] == pytest.approx(50e9 / 32)
        assert ceil["ceiling_tok_s"] == pytest.approx(64.0)
        assert ceil["measured_tok_s"] == pytest.approx(16.0)
        assert ceil["frac_of_ceiling"] == pytest.approx(
            rep["hbm"]["bw_util"])
        s = led.summary(now=102.0)
        assert s["attention_kernel"] == "pallas_dense"
        assert s["ceiling_tok_s"] == pytest.approx(64.0)
        assert s["frac_of_ceiling"] == pytest.approx(0.25)

    def test_peaks_count_the_engines_devices_not_the_hosts(self,
                                                           monkeypatch):
        """A one-chip engine on a four-chip host: the roofline
        denominators are one chip's peaks (they used to be multiplied
        by len(jax.local_devices()) — a 4x denominator)."""
        import jax

        class FakeDev:
            platform = "tpu"
            device_kind = "TPU v5 lite"

        host = [FakeDev() for _ in range(4)]
        monkeypatch.setattr(jax, "local_devices", lambda: host)
        monkeypatch.setattr(jax, "devices", lambda: host)
        monkeypatch.delenv("PERF_PEAK_TFLOPS", raising=False)
        monkeypatch.delenv("PERF_PEAK_HBM_GBPS", raising=False)
        tr = Tracer(enabled=True)
        _step(tr, 100.0, 101.0)
        led = _ledger(tr)
        led.bind_model(TINY, num_slots=4, dtype="bfloat16",
                       devices=host[:1])
        rep = led.report(now=101.0)
        assert rep["mfu"]["device"] == "TPU v5 lite"
        assert rep["mfu"]["peak_tflops"] == pytest.approx(197.0)
        assert led._peak_hbm() == (pytest.approx(819.0), "TPU v5 lite")
        # A tp=4 mesh engine on the same host sums its four devices.
        led.bind_model(TINY, num_slots=4, dtype="bfloat16", devices=host)
        assert led.report(now=101.0)["mfu"]["peak_tflops"] == \
            pytest.approx(4 * 197.0)
        # A kind the table does not know: null, never a default.
        FakeDev.device_kind = "TPU v9 imaginary"
        led.bind_model(TINY, num_slots=4, dtype="bfloat16",
                       devices=host[:1])
        assert led.report(now=101.0)["mfu"]["peak_tflops"] is None

    def test_ceiling_null_without_peak(self):
        # CPU / unknown device: nulls, never a made-up ceiling.
        tr = Tracer(enabled=True)
        _step(tr, 100.0, 101.0)
        rep = _ledger(tr).report(now=101.0)
        assert rep["ceiling"]["ceiling_tok_s"] is None
        assert rep["ceiling"]["frac_of_ceiling"] is None


class TestPerfSurfaces:
    async def _client(self):
        from fasttalk_tpu.monitoring.monitor import build_monitoring_app

        client = TestClient(TestServer(build_monitoring_app()))
        await client.start_server()
        return client

    def _seed_global(self):
        import time

        tr = get_tracer()
        now = time.monotonic()
        tr.step("engine_step", now - 1.0, now - 0.5, steps=8, batch=2,
                slots=4, occupancy=0.5, kind="plain", tokens=16,
                rows=32, kv_len=512, flops=1e9)
        tr.step("engine_prefill", now - 0.4, now - 0.3, bucket=64,
                tokens=40, rows=64, kind="batched")

    async def test_get_perf_decomposition(self):
        self._seed_global()
        client = await self._client()
        try:
            r = await client.get("/perf")
            assert r.status == 200
            body = await r.json()
            wall = body["wall"]
            # The acceptance bar: components sum to ~100% of the
            # engine wall window, plus a padding-waste fraction.
            assert wall["device_busy_frac"] + wall["host_gap_frac"] \
                + wall["idle_frac"] == pytest.approx(1.0, abs=0.01)
            assert 0.0 <= body["tokens"]["padding_waste_frac"] <= 1.0
            assert body["mfu"]["achieved_tflops"] > 0
        finally:
            await client.close()

    async def test_perf_gauges_render_valid_exposition(self):
        """The new perf_* gauges must render as scrapeable exposition
        (satellite: check_prometheus over the live /metrics)."""
        self._seed_global()
        client = await self._client()
        try:
            r = await client.get("/metrics")
            text = await r.text()
        finally:
            await client.close()
        problems = check_prometheus.validate(text)
        assert not problems, problems
        for gauge in ("perf_device_busy_frac", "perf_host_gap_frac",
                      "perf_idle_frac", "perf_padding_waste_frac",
                      "perf_occupancy", "perf_useful_tok_s",
                      "perf_mfu", "perf_peak_tflops"):
            assert f"# TYPE {gauge} gauge" in text, gauge
        assert "perf_serving_compiles_total" in text

    def test_trace_report_perf_section(self, tmp_path, capsys):
        dump = tmp_path / "dump.jsonl"
        rows = [
            {"request_id": None, "session_id": "", "span": "engine_step",
             "ts": 100.0, "dur_ms": 1000.0,
             "attrs": {"steps": 8, "batch": 2, "slots": 4,
                       "occupancy": 0.5, "tokens": 16, "rows": 32,
                       "kv_len": 512, "flops": 1e9}},
            {"request_id": None, "session_id": "",
             "span": "engine_prefill", "ts": 101.1, "dur_ms": 100.0,
             "attrs": {"bucket": 64, "tokens": 40, "rows": 64}},
            {"request_id": "r1", "session_id": "s1", "span": "prefill",
             "ts": 100.0, "dur_ms": 30.0, "attrs": {}},
        ]
        dump.write_text("".join(json.dumps(r) + "\n" for r in rows))
        assert trace_report.main(["--perf", str(dump)]) == 0
        out = capsys.readouterr().out
        assert "perf attribution" in out
        assert "device busy" in out
        assert "padding waste" in out
        # And the module-level math agrees with the in-process ledger:
        # busy 1.1 s, 0.1 s host gap, window 1.2 s; waste 1 - 56/96.
        p = trace_report.perf_attribution(rows, idle_gap_ms=250.0)
        assert p["device_busy_frac"] == pytest.approx(1.1 / 1.2,
                                                      abs=1e-3)
        assert p["host_gap_frac"] == pytest.approx(0.1 / 1.2, abs=1e-3)
        assert p["idle_frac"] == pytest.approx(0.0, abs=1e-3)
        assert p["padding_waste_frac"] == pytest.approx(1 - 56 / 96,
                                                        abs=1e-3)

    def test_trace_report_perf_without_engine_rows(self, tmp_path,
                                                   capsys):
        dump = tmp_path / "d.jsonl"
        dump.write_text(json.dumps(
            {"request_id": "r", "session_id": "s", "span": "prefill",
             "ts": 1.0, "dur_ms": 2.0, "attrs": {}}) + "\n")
        assert trace_report.main(["--perf", str(dump)]) == 0
        assert "no engine_step/engine_prefill rows" \
            in capsys.readouterr().out


class _FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def _recorder(tmp_path, clock, **kw):
    kw.setdefault("enabled", True)
    kw.setdefault("max_bundles", 8)
    kw.setdefault("min_interval_s", 120.0)
    kw.setdefault("autoprof_s", 0.0)
    kw.setdefault("recompile_burst", 3)
    kw.setdefault("recompile_window_s", 60.0)
    kw.setdefault("events_tail", 64)
    kw.setdefault("config_provider",
                  lambda: {"model_name": "tiny",
                           "vllm_api_key": "sk-secret",
                           "tokenizer_path": "/models/tok"})
    return FlightRecorder(base_dir=str(tmp_path / "flight"),
                          clock=clock, inline=True, **kw)


class TestFlightRecorder:
    def test_page_event_writes_exactly_one_bundle(self, tmp_path):
        """The acceptance test: a synthetic SLO page event produces
        exactly ONE rate-limited bundle containing events, traces and
        the perf snapshot — fake clock, zero sleeps."""
        clock = _FakeClock()
        events = EventLog(ring_size=64, jsonl_path="", clock=clock)
        rec = _recorder(tmp_path, clock)
        rec.install(events)
        # Give the singleton tracer something to snapshot.
        tr = get_tracer()
        tr.start("fr-1", "fs-1")
        tr.add_span("fr-1", "queue_wait", 1.0, 1.01)
        tr.finish("fr-1")
        tr.step("engine_step", 1.0, 1.2, steps=8, batch=1, slots=4,
                occupancy=0.25, tokens=8, rows=32, kv_len=512)

        events.emit("slo_burn_start", severity="critical",
                    cls="interactive", state="page", objective="ttft")
        clock.t += 5.0  # a page storm: second page 5 s later
        events.emit("slo_burn_start", severity="critical",
                    cls="bulk", state="page", objective="ttft")

        bundles = rec.list_bundles()
        assert len(bundles) == 1, bundles
        assert rec.bundles_written == 1
        assert rec.triggers_suppressed == 1
        b = bundles[0]
        for name in ("manifest.json", "events.json", "trace.json",
                     "trace.jsonl", "perf.json", "metrics.prom",
                     "metrics.json", "slo.json", "config.json"):
            assert os.path.isfile(os.path.join(b, name)), name
        with open(os.path.join(b, "events.json")) as fp:
            evs = json.load(fp)
        assert any(e["kind"] == "slo_burn_start" for e in evs)
        with open(os.path.join(b, "trace.jsonl")) as fp:
            spans = [json.loads(x) for x in fp if x.strip()]
        assert any(s["span"] == "engine_step" for s in spans)
        assert any(s["request_id"] == "fr-1" for s in spans)
        with open(os.path.join(b, "perf.json")) as fp:
            perf = json.load(fp)
        assert "wall" in perf and "compiles" in perf
        with open(os.path.join(b, "manifest.json")) as fp:
            manifest = json.load(fp)
        assert manifest["reason"] == "slo_page:interactive"
        assert "errors" not in manifest
        rec.uninstall()

    def test_warn_burn_does_not_trigger(self, tmp_path):
        clock = _FakeClock()
        events = EventLog(ring_size=16, jsonl_path="", clock=clock)
        rec = _recorder(tmp_path, clock)
        rec.install(events)
        events.emit("slo_burn_start", severity="warning",
                    cls="interactive", state="warn")
        assert rec.list_bundles() == []
        rec.uninstall()

    def test_stall_and_restart_trigger(self, tmp_path):
        clock = _FakeClock()
        events = EventLog(ring_size=16, jsonl_path="", clock=clock)
        rec = _recorder(tmp_path, clock)
        rec.install(events)
        events.emit("stall_detected", severity="critical",
                    stall="engine_step")
        assert len(rec.list_bundles()) == 1
        clock.t += 300.0  # past the rate limit
        events.emit("engine_restart", severity="critical")
        assert len(rec.list_bundles()) == 2
        rec.uninstall()

    def test_recompile_burst_threshold(self, tmp_path):
        clock = _FakeClock()
        events = EventLog(ring_size=16, jsonl_path="", clock=clock)
        rec = _recorder(tmp_path, clock, recompile_burst=3)
        rec.install(events)
        events.emit("recompile", what="decode")
        clock.t += 1.0
        events.emit("recompile", what="decode")
        assert rec.list_bundles() == []  # two compiles: not a burst
        clock.t += 1.0
        events.emit("recompile", what="prefill")
        assert len(rec.list_bundles()) == 1
        rec.uninstall()

    def test_rate_limit_lifts_after_interval(self, tmp_path):
        clock = _FakeClock()
        rec = _recorder(tmp_path, clock, min_interval_s=120.0)
        assert rec.trigger("one") is not None
        clock.t += 60.0
        assert rec.trigger("two") is None      # still inside the limit
        clock.t += 61.0
        assert rec.trigger("three") is not None
        assert len(rec.list_bundles()) == 2

    def test_manual_force_bypasses_without_consuming_limit(
            self, tmp_path):
        clock = _FakeClock()
        rec = _recorder(tmp_path, clock)
        assert rec.trigger("auto") is not None
        assert rec.trigger("manual", force=True) is not None
        assert len(rec.list_bundles()) == 2
        # A forced capture must not refresh the rate-limit window: an
        # operator's curl right before a real incident would otherwise
        # suppress the automatic capture.
        clock.t += 121.0
        assert rec.trigger("manual2", force=True) is not None
        clock.t += 1.0  # window measured from "auto", long expired
        assert rec.trigger("auto2") is not None
        assert len(rec.list_bundles()) == 4

    def test_retention_prunes_oldest(self, tmp_path):
        clock = _FakeClock()
        rec = _recorder(tmp_path, clock, max_bundles=2)
        for i in range(3):
            clock.t += 200.0
            assert rec.trigger(f"b{i}", force=True) is not None
        assert len(rec.list_bundles()) == 2
        reasons = set()
        for b in rec.list_bundles():
            with open(os.path.join(b, "manifest.json")) as fp:
                reasons.add(json.load(fp)["reason"])
        assert reasons == {"b1", "b2"}  # b0 pruned

    def test_mkdir_failure_does_not_consume_limit(self, tmp_path):
        clock = _FakeClock()
        rec = _recorder(tmp_path, clock)
        blocker = tmp_path / "flight"
        blocker.write_text("a file squatting the bundle dir")
        assert rec.trigger("fails") is None  # nothing written...
        blocker.unlink()
        # ...so the very next trigger (disk recovered) still captures —
        # the failed attempt must not eat the rate-limit window.
        assert rec.trigger("works") is not None

    def test_disabled_never_writes(self, tmp_path):
        rec = _recorder(tmp_path, _FakeClock(), enabled=False)
        assert rec.trigger("x", force=True) is None
        assert rec.list_bundles() == []

    def test_config_redaction(self, tmp_path):
        clock = _FakeClock()
        rec = _recorder(tmp_path, clock)
        b = rec.trigger("redact", force=True)
        with open(os.path.join(b, "config.json")) as fp:
            cfg = json.load(fp)
        assert cfg["vllm_api_key"] == "***"
        assert cfg["tokenizer_path"] == "/models/tok"  # a path, kept
        assert cfg["model_name"] == "tiny"

    def test_redact_config_unit(self):
        out = redact_config({"api_key": "abc", "hf_token": "xyz",
                             "log_path": "./logs", "port": 8000,
                             "vllm_api_key": "",
                             # Slash-bearing credentials (base64/JWT)
                             # must still redact: the exemption is by
                             # field name, never by value shape.
                             "access_key": "ab/cd==",
                             "tokenizer_path": "/models/tok",
                             "secret_dir": "/run/secrets"})
        assert out["api_key"] == "***"
        assert out["hf_token"] == "***"
        assert out["access_key"] == "***"
        assert out["log_path"] == "./logs"
        assert out["port"] == 8000
        assert out["vllm_api_key"] == ""  # empty: nothing to hide
        assert out["tokenizer_path"] == "/models/tok"  # *_path exempt
        assert out["secret_dir"] == "/run/secrets"     # *_dir exempt

    def test_broken_section_is_isolated(self, tmp_path):
        clock = _FakeClock()
        rec = _recorder(tmp_path, clock,
                        config_provider=lambda: 1 / 0)
        b = rec.trigger("broken", force=True)
        assert os.path.isfile(os.path.join(b, "events.json"))
        assert not os.path.isfile(os.path.join(b, "config.json"))
        with open(os.path.join(b, "manifest.json")) as fp:
            manifest = json.load(fp)
        assert "config.json" in manifest["errors"]

    async def test_manual_bundle_endpoint(self, tmp_path, monkeypatch):
        from fasttalk_tpu.monitoring.monitor import build_monitoring_app
        import fasttalk_tpu.observability.flight as flight_mod

        clock = _FakeClock()
        rec = _recorder(tmp_path, clock)
        monkeypatch.setattr(flight_mod, "_flight", rec)
        client = TestClient(TestServer(build_monitoring_app()))
        await client.start_server()
        try:
            r = await client.post("/debug/bundle")
            assert r.status == 200
            body = await r.json()
            assert body["dir"].startswith(str(tmp_path))
            assert os.path.isfile(
                os.path.join(body["dir"], "manifest.json"))
            assert body["bundles_written"] == 1
        finally:
            await client.close()

    async def test_manual_bundle_endpoint_disabled(self, tmp_path,
                                                   monkeypatch):
        from fasttalk_tpu.monitoring.monitor import build_monitoring_app
        import fasttalk_tpu.observability.flight as flight_mod

        rec = _recorder(tmp_path, _FakeClock(), enabled=False)
        monkeypatch.setattr(flight_mod, "_flight", rec)
        client = TestClient(TestServer(build_monitoring_app()))
        await client.start_server()
        try:
            assert (await client.post("/debug/bundle")).status == 409
        finally:
            await client.close()

    def test_singletons_and_reset(self):
        assert get_flight() is get_flight()
        assert get_perf() is get_perf()


class TestPerfFlightConfig:
    def _config(self, **kw):
        from fasttalk_tpu.utils.config import Config

        return Config(llm_provider="fake", compute_device="cpu", **kw)

    def test_defaults_valid_and_surfaced(self):
        cfg = self._config()
        d = cfg.to_dict()
        for key in ("perf_window_s", "perf_idle_gap_ms",
                    "perf_peak_tflops", "flight_enabled", "flight_dir",
                    "flight_max_bundles", "flight_min_interval_s",
                    "flight_autoprof_s", "flight_recompile_burst",
                    "flight_recompile_window_s", "flight_events_tail"):
            assert key in d, key  # `main.py config --show` surface

    @pytest.mark.parametrize("kw", [
        {"perf_window_s": 0.0},
        {"perf_idle_gap_ms": -1.0},
        {"perf_peak_tflops": -1.0},
        {"flight_dir": "  "},
        {"flight_max_bundles": 0},
        {"flight_min_interval_s": -1.0},
        {"flight_autoprof_s": -0.5},
        {"flight_recompile_burst": 1},
        {"flight_recompile_window_s": 0.0},
        {"flight_events_tail": 0},
    ])
    def test_invalid_knobs_rejected(self, kw):
        with pytest.raises(ValueError):
            self._config(**kw)

    def test_env_knobs_resolve(self, monkeypatch):
        monkeypatch.setenv("PERF_WINDOW_S", "30")
        monkeypatch.setenv("FLIGHT_MAX_BUNDLES", "3")
        monkeypatch.setenv("FLIGHT_AUTOPROF_S", "2.5")
        cfg = self._config()
        assert cfg.perf_window_s == 30.0
        assert cfg.flight_max_bundles == 3
        assert cfg.flight_autoprof_s == 2.5
