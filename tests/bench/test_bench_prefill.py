"""The reader of the serve engine's batched prefill span."""
import pytest

from bench import manifest

MS = 1e6


def _read(pt):
    res = {"trace": {"host": [], "devices": []}, "progtrace": pt}
    return manifest.load_metric("serve_prefill_ms").read({}, res)


def test_prefill_is_the_mean_prefill_step_span_in_the_window():
    spans = []
    for t0, length in ((10, 20), (60, 30), (200, 50)):
        spans += [("serve.prefill", t0 * MS, (t0 + length + 1) * MS),
                  ("serve.prefill_step", t0 * MS, (t0 + length) * MS),
                  ("serve.sync", (t0 + 2) * MS, (t0 + length) * MS)]
    pt = {"spans": sorted(spans, key=lambda s: (s[1], -s[2])),
          "window": (5 * MS, 150 * MS), "hlo": {}}
    assert _read(pt) == pytest.approx((20 + 30) / 2)


def test_a_token_by_token_prefill_reads_nothing():
    pt = {"spans": [("serve.prefill", 0, 9 * MS), ("serve.step", 0, 4 * MS),
                    ("serve.step", 5 * MS, 9 * MS)],
          "window": (0.0, 10 * MS), "hlo": {}}
    assert _read(pt) is None
    assert _read({"spans": [], "window": None, "hlo": {}}) is None
    assert manifest.load_metric("serve_prefill_ms").read({}, {}) is None
