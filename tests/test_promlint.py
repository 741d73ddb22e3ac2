"""Prometheus exposition self-lint (ISSUE 16).

Every ``/metrics`` producer builds its dict by merging sources
(engine stats, fleet manager counters, admission stats, goodput)
— so one renamed key can silently demote a counter to a
gauge or collide two series after nested-dict flattening. These tests
walk each REAL producer's rendered text through
``promtext.lint_exposition`` so the naming contract (counters end
``_total``, histograms are complete ``_bucket``/``_sum``/``_count``
families, no duplicate names) is enforced at the choke point instead
of per-field assertions that rot.
"""
import pytest

jax = pytest.importorskip("jax")

from pytorch_distributed_template_tpu.fleet.admission import (  # noqa: E402
    FairAdmission,
)
from pytorch_distributed_template_tpu.fleet.replicas import (  # noqa: E402
    FleetManager, Replica,
)
from pytorch_distributed_template_tpu.fleet.router import (  # noqa: E402
    RouterStats, router_metrics,
)
from pytorch_distributed_template_tpu.utils import promtext  # noqa: E402


# ---------------------------------------------------------------------------
# the lint itself (synthetic expositions)
# ---------------------------------------------------------------------------


def test_lint_clean_text_passes():
    text = promtext.prometheus_text(
        {"requests_total": 3, "queue_depth": 1,
         "latency": {"p50_s": 0.1},
         "ttft_seconds": promtext.zero_histogram()})
    assert promtext.lint_exposition(text) == []


def test_lint_counter_without_total_suffix():
    bad = ("# TYPE pdt_serve_requests counter\n"
           "pdt_serve_requests 3\n")
    out = promtext.lint_exposition(bad)
    assert any("without _total suffix" in v for v in out), out


def test_lint_gauge_named_total_is_demoted_counter():
    bad = ("# TYPE pdt_serve_tokens_total gauge\n"
           "pdt_serve_tokens_total 3\n")
    out = promtext.lint_exposition(bad)
    assert any("demoted counter" in v for v in out), out


def test_lint_duplicate_series_from_flatten_collision():
    # the exact failure mode the lint exists for: a nested dict
    # ("latency" -> latency_p50_s) flattening onto a top-level key
    text = promtext.prometheus_text(
        {"latency_p50_s": 0.2, "latency": {"p50_s": 0.1}})
    out = promtext.lint_exposition(text)
    assert any("duplicate" in v for v in out), out


def test_lint_incomplete_histogram():
    bad = ("# TYPE pdt_serve_ttft_seconds histogram\n"
           'pdt_serve_ttft_seconds_bucket{le="+Inf"} 2\n'
           "pdt_serve_ttft_seconds_sum 0.4\n")       # _count missing
    out = promtext.lint_exposition(bad)
    assert any("incomplete histogram" in v for v in out), out


def test_lint_histogram_inf_bucket_must_equal_count():
    bad = ("# TYPE pdt_serve_ttft_seconds histogram\n"
           'pdt_serve_ttft_seconds_bucket{le="0.1"} 1\n'
           'pdt_serve_ttft_seconds_bucket{le="+Inf"} 1\n'
           "pdt_serve_ttft_seconds_sum 0.4\n"
           "pdt_serve_ttft_seconds_count 2\n")
    out = promtext.lint_exposition(bad)
    assert any("+Inf bucket" in v for v in out), out


def test_lint_histogram_buckets_cumulative():
    bad = ("# TYPE pdt_serve_ttft_seconds histogram\n"
           'pdt_serve_ttft_seconds_bucket{le="0.1"} 3\n'
           'pdt_serve_ttft_seconds_bucket{le="0.5"} 1\n'
           'pdt_serve_ttft_seconds_bucket{le="+Inf"} 3\n'
           "pdt_serve_ttft_seconds_sum 0.4\n"
           "pdt_serve_ttft_seconds_count 3\n")
    out = promtext.lint_exposition(bad)
    assert any("not cumulative" in v for v in out), out


def test_lint_undeclared_sample():
    bad = "pdt_serve_orphan 1\n"
    out = promtext.lint_exposition(bad)
    assert any("without TYPE" in v for v in out), out


# ---------------------------------------------------------------------------
# real producers
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def live_service():
    """A real continuous-batching service that has served traffic, so
    service_metrics walks every hasattr branch it has (histograms,
    prefix cache, brownout)."""
    import numpy as np

    import pytorch_distributed_template_tpu.models  # noqa: F401
    from pytorch_distributed_template_tpu.engine.continuous import (
        ContinuousBatchingService,
    )
    from pytorch_distributed_template_tpu.config.registry import (
        MODELS,
    )

    model = MODELS.get("Llama")(
        vocab_size=64, n_layer=2, n_head=4, n_kv_head=2,
        d_model=32, max_len=128)
    params = model.init(
        jax.random.key(0),
        jax.numpy.zeros((1, 8), jax.numpy.int32))["params"]
    svc = ContinuousBatchingService.from_model(
        model, params, slots=2, chunk=4, window_ms=10.0)
    rs = np.random.RandomState(0)
    svc.generate(prompt_ids=[int(x) for x in rs.randint(1, 64, 6)],
                 max_new_tokens=4)
    return svc


def test_serve_metrics_exposition_lints_clean(live_service):
    import serve

    metrics = serve.service_metrics(live_service)
    text = serve.prometheus_text(metrics)
    assert promtext.lint_exposition(text) == []


def test_serve_metrics_with_auditor_lints_clean(live_service,
                                                tmp_path):
    """The token-integrity families (ISSUE 18) ride service_metrics:
    a live auditor that has matched AND diverged emits serve_path_*,
    audit_path_* and the audit verdict counters — all lint-clean (the
    fingerprint embeds in the metric NAME, so a malformed fingerprint
    would fail the lint, not just look odd)."""
    import serve
    from pytorch_distributed_template_tpu.observability.audit import (
        ShadowAuditor,
    )

    aud = ShadowAuditor(lambda rec: [1, 2, 3], sample_rate=1.0,
                        floor=4, dump_dir=tmp_path, cooldown_s=0.0)
    base = {"stop_reason": "length", "prompt_ids": [5],
            "max_new_tokens": 3, "temperature": 0.0, "top_k": 0,
            "top_p": 0.0, "seed": 0, "stop": None}
    aud.offer(dict(base, rid="m1", serve_path="warm_adopt",
                   ids=[1, 2, 3]))
    aud.offer(dict(base, rid="d1", serve_path="paged_ship",
                   ids=[1, 9, 3]))
    assert aud.drain(timeout_s=30.0)
    try:
        metrics = serve.service_metrics(live_service, auditor=aud)
        text = serve.prometheus_text(metrics)
        assert promtext.lint_exposition(text) == []
        for family in ("token_divergence_total",
                       "audit_sampled_total",
                       "serve_path_", "audit_path_paged_ship"):
            assert family in text, family
    finally:
        aud.close()


def test_router_metrics_exposition_lints_clean(tmp_path):
    # an UNPOLLED manager: counter keys are static (zeros), which is
    # exactly what the lint needs — names, not values
    manager = FleetManager(
        [Replica("r0", url="http://127.0.0.1:9")],
        run_dir=tmp_path, snapshot_every=0)
    admission = FairAdmission(manager.capacity)
    metrics = router_metrics(manager, admission, RouterStats())
    text = promtext.prometheus_text(metrics, prefix="pdt_fleet")
    assert promtext.lint_exposition(text) == []
