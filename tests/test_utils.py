"""Unit tests for L0 utils: config, errors, metrics, logger."""

import time

import pytest

from fasttalk_tpu.utils.config import (ComputeDeviceError, Config,
                                       detect_compute_device)
from fasttalk_tpu.utils.errors import (
    CircuitBreaker,
    CircuitBreakerOpen,
    CircuitState,
    ErrorCategory,
    ErrorHandler,
    ErrorSeverity,
    LLMServiceError,
    RetryManager,
)
from fasttalk_tpu.utils.logger import get_logger
from fasttalk_tpu.utils.metrics import get_metrics


class TestConfig:
    def test_defaults_valid(self, monkeypatch):
        monkeypatch.delenv("COMPUTE_DEVICE", raising=False)
        cfg = Config()
        assert cfg.llm_provider == "tpu"
        assert cfg.compute_device == "auto"  # the request, unresolved
        assert cfg.decode_slots == 16

    def test_env_overrides(self, monkeypatch):
        monkeypatch.setenv("DEFAULT_TEMPERATURE", "0.3")
        monkeypatch.setenv("TPU_DECODE_SLOTS", "4")
        monkeypatch.setenv("LLM_MODEL", "llama3:8b")
        cfg = Config()
        assert cfg.default_temperature == 0.3
        assert cfg.decode_slots == 4
        assert cfg.model_name == "llama3:8b"

    def test_invalid_temperature_rejected(self, monkeypatch):
        monkeypatch.setenv("DEFAULT_TEMPERATURE", "5.0")
        with pytest.raises(ValueError, match="temperature"):
            Config()

    def test_invalid_provider_rejected(self, monkeypatch):
        monkeypatch.setenv("LLM_PROVIDER", "nonsense")
        with pytest.raises(ValueError, match="llm_provider"):
            Config()

    def test_port_clash_rejected(self, monkeypatch):
        monkeypatch.setenv("LLM_PORT", "9092")
        with pytest.raises(ValueError, match="monitoring_port"):
            Config()

    def test_prefill_chunk_power_of_two(self, monkeypatch):
        monkeypatch.setenv("TPU_PREFILL_CHUNK", "100")
        with pytest.raises(ValueError, match="power of two"):
            Config()

    def test_device_detection_respects_env(self, monkeypatch):
        monkeypatch.setenv("COMPUTE_DEVICE", "cpu")
        assert detect_compute_device() == "cpu"

    def test_device_detection_rejects_bogus(self, monkeypatch):
        monkeypatch.setenv("COMPUTE_DEVICE", "quantum")
        with pytest.raises(ComputeDeviceError, match="quantum"):
            detect_compute_device()
        with pytest.raises(ValueError, match="compute_device"):
            Config()

    def test_auto_detection_picks_an_available_device(self, monkeypatch):
        monkeypatch.delenv("COMPUTE_DEVICE", raising=False)
        assert detect_compute_device() == "cpu"  # conftest: JAX on cpu

    def test_explicit_tpu_without_a_tpu_is_a_named_error(self,
                                                         monkeypatch):
        """COMPUTE_DEVICE=tpu on a machine whose JAX sees no TPU must
        not resolve to another device (it used to serve on the CPU)."""
        monkeypatch.setenv("COMPUTE_DEVICE", "tpu")
        with pytest.raises(ComputeDeviceError, match="no tpu device"):
            detect_compute_device()

    def test_backend_failure_is_carried_not_swallowed(self, monkeypatch):
        """A backend that is present but unusable ("TPU already in use
        by another process") surfaces with its own message, also in
        auto mode — never as a quiet 'cpu'."""
        import jax

        def boom():
            raise RuntimeError("TPU is already in use by pid 1234")

        monkeypatch.setattr(jax, "devices", boom)
        for requested in ("tpu", "auto"):
            with pytest.raises(ComputeDeviceError,
                               match="already in use") as ei:
                detect_compute_device(requested)
            assert isinstance(ei.value.__cause__, RuntimeError)

    def test_config_construction_touches_no_backend(self):
        """Building a Config — in an orchestrating parent, or `main.py
        config --show` beside a running server — must not claim the
        chip: it does not even import jax."""
        import subprocess
        import sys

        code = ("import sys\n"
                "from fasttalk_tpu.utils.config import Config\n"
                "cfg = Config(); cfg.to_dict()\n"
                "assert 'jax' not in sys.modules, 'Config imported jax'\n")
        p = subprocess.run([sys.executable, "-c", code], text=True,
                           capture_output=True, timeout=60,
                           env={**__import__("os").environ,
                                "COMPUTE_DEVICE": "tpu"})
        assert p.returncode == 0, p.stderr[-1500:]

    def test_presets(self):
        cfg = Config()
        cfg.apply_preset("fast")
        assert cfg.default_max_tokens == 512
        cfg.apply_preset("quality")
        assert cfg.default_max_tokens == 4096
        with pytest.raises(ValueError):
            cfg.apply_preset("warp")

    def test_to_dict_round_trip(self):
        d = Config().to_dict()
        assert "compute_device" in d and "decode_slots" in d


class TestErrors:
    def test_error_to_dict(self):
        e = LLMServiceError("boom", category=ErrorCategory.MODEL,
                            severity=ErrorSeverity.HIGH, recoverable=False)
        d = e.to_dict()
        assert d["code"] == "model_error"
        assert d["severity"] == "high"
        assert d["recoverable"] is False

    def test_circuit_breaker_opens_and_recovers(self):
        cb = CircuitBreaker(failure_threshold=2, reset_timeout=0.05,
                            half_open_successes=1)
        cb.check()
        cb.record_failure()
        cb.record_failure()
        assert cb.state is CircuitState.OPEN
        with pytest.raises(CircuitBreakerOpen) as ei:
            cb.check()
        assert ei.value.retry_after is not None
        time.sleep(0.06)
        assert cb.state is CircuitState.HALF_OPEN
        cb.check()  # allowed in half-open
        cb.record_success()
        assert cb.state is CircuitState.CLOSED

    def test_circuit_breaker_reopens_from_half_open(self):
        cb = CircuitBreaker(failure_threshold=1, reset_timeout=0.01)
        cb.record_failure()
        time.sleep(0.02)
        assert cb.state is CircuitState.HALF_OPEN
        cb.record_failure()
        assert cb.state is CircuitState.OPEN

    def test_retry_succeeds_after_failures(self):
        rm = RetryManager(max_attempts=3, base_delay=0.001)
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise ConnectionError("refused")
            return "ok"

        assert rm.retry_with_backoff(flaky) == "ok"
        assert len(calls) == 3

    def test_retry_gives_up(self):
        rm = RetryManager(max_attempts=2, base_delay=0.001)
        with pytest.raises(ValueError):
            rm.retry_with_backoff(lambda: (_ for _ in ()).throw(ValueError("nope")))

    def test_retry_respects_non_recoverable(self):
        rm = RetryManager(max_attempts=5, base_delay=0.001)
        calls = []

        def fatal():
            calls.append(1)
            raise LLMServiceError("fatal", recoverable=False)

        with pytest.raises(LLMServiceError):
            rm.retry_with_backoff(fatal)
        assert len(calls) == 1

    def test_handler_categorizes_foreign_exceptions(self):
        h = ErrorHandler()
        e = h.handle_error(TimeoutError("request timed out"))
        assert e.category is ErrorCategory.TIMEOUT
        e = h.handle_error(ConnectionError("connection refused"))
        assert e.category is ErrorCategory.CONNECTION
        e = h.handle_error(MemoryError("out of memory"))
        assert e.category is ErrorCategory.RESOURCE
        stats = h.get_error_stats()
        assert stats["total_errors"] == 3
        assert stats["by_category"]["timeout_error"] == 1
        assert len(stats["recent"]) == 3


class TestMetrics:
    def test_counters_gauges(self):
        m = get_metrics()
        m.counter("requests_total").inc()
        m.counter("requests_total").inc(2)
        m.gauge("active").set(5)
        m.gauge("active").dec()
        d = m.to_dict()
        assert d["requests_total"] == 3
        assert d["active"] == 4

    def test_histogram_percentiles(self):
        m = get_metrics()
        h = m.histogram("ttft_ms")
        for v in range(1, 101):
            h.observe(float(v))
        s = h.summary()
        assert s["count"] == 100
        assert 45 <= s["p50"] <= 55
        assert 90 <= s["p95"] <= 100

    def test_prometheus_output(self):
        m = get_metrics()
        m.counter("tok_total", "tokens").inc(7)
        m.histogram("lat_ms").observe(12.0)
        text = m.prometheus()
        assert "# TYPE tok_total counter" in text
        assert "tok_total 7" in text
        assert 'lat_ms_bucket{le="+Inf"} 1' in text

    def test_type_clash_raises(self):
        m = get_metrics()
        m.counter("x")
        with pytest.raises(TypeError):
            m.gauge("x")


def test_logger_smoke(capsys):
    log = get_logger("test")
    log.info("hello", foo=1)
    log.log_generation("sess-1", tokens=10, duration_s=0.5, ttft_ms=42.0)
    log.error("bad", exc_info=False)
