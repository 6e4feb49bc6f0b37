"""The readers of the program's own spans and op scopes: time to first
token, gap between tokens, first step, dispatch, optimizer and batch time,
and the device's idle time by the innermost program span."""
import pytest

from bench import devtrace, manifest, progtrace

MS = 1e6


def _round(t0, prompt, new, step=4, sync=3, first=50, gap=1):
    """One ``serve.generate`` span from ``t0`` (ms): 1 ms of set-up, then
    ``prompt`` steps in ``serve.prefill`` and ``new - 1`` in
    ``serve.decode``, each step ``step`` ms holding a ``sync`` ms
    ``serve.sync`` at its end, ``gap`` ms between steps; the round's first
    step lasts ``first`` ms and holds a ``serve.trace_step``.  Returns
    the spans and the round's end."""
    spans, t = [], t0 + 1
    for part, n in (("prefill", prompt), ("decode", new - 1)):
        start = t
        for i in range(n):
            length = first if not spans else step
            spans.append(("serve.step", t * MS, (t + length) * MS))
            if length == first:
                spans.append(("serve.trace_step", (t + 1) * MS,
                              (t + 2) * MS))
            spans.append(("serve.sync", (t + length - sync) * MS,
                          (t + length) * MS))
            t += length + (gap if i < n - 1 else 0)
        spans.append((f"serve.{part}", start * MS, t * MS))
    t += 2                                   # the final stack
    spans.append(("serve.generate", t0 * MS, t * MS))
    return spans, t


def _serve_trace(n_rounds=2, **kw):
    spans, t = [], 10
    for _ in range(n_rounds):
        got, t = _round(t, 3, 4, **kw)
        spans += got
        t += 5                               # bench.input and the rest
    return {"spans": sorted(spans, key=lambda s: (s[1], -s[2])),
            "window": (0.0, (t + 10) * MS), "hlo": {}}


def _read(name, pt, trace=None):
    res = {"trace": trace or {"host": [], "devices": []}, "progtrace": pt}
    return manifest.load_metric(name).read({}, res)


def test_a_round_has_its_prefill_and_decode_steps():
    (r1, r2) = progtrace.rounds(_serve_trace())
    assert len(r1["prefill_steps"]) == 3 and len(r1["decode_steps"]) == 3
    assert all(len(syncs) == 1 for _, syncs in
               r1["prefill_steps"] + r1["decode_steps"])
    assert r1["generate"][1] < r1["prefill"][1] < r1["decode"][1]


def test_ttft_runs_from_the_call_to_the_end_of_prefill():
    # 1 ms set-up, a 50 ms first step, two of 4 ms, two 1 ms gaps
    assert _read("serve_ttft_ms", _serve_trace()) == pytest.approx(
        1 + 50 + 4 + 4 + 2)


def test_token_gap_is_decode_over_its_steps():
    # three 4 ms steps and two 1 ms gaps over three steps
    assert _read("serve_token_gap_ms", _serve_trace()) == pytest.approx(
        14 / 3)


def test_first_step_is_each_rounds_first():
    assert _read("serve_first_step_ms", _serve_trace(first=30)
                 ) == pytest.approx(30)


def test_dispatch_leaves_out_the_sync_and_each_rounds_first_step():
    assert _read("serve_dispatch_ms", _serve_trace(step=4, sync=3)
                 ) == pytest.approx(1.0)


def test_rounds_outside_the_window_are_left_out():
    pt = _serve_trace(n_rounds=2)
    first_end = max(s[2] for s in pt["spans"] if s[0] == "serve.generate"
                    and s[1] == 10 * MS)
    pt["window"] = (0.0, first_end)
    assert len(progtrace.rounds(pt)) == 1


def test_a_trace_without_program_spans_reads_nothing():
    pt = {"spans": [], "window": (0.0, 10 * MS), "hlo": {}}
    for name in ("serve_ttft_ms", "serve_token_gap_ms",
                 "serve_first_step_ms", "serve_dispatch_ms",
                 "train_batch_ms"):
        assert _read(name, pt) is None
    assert manifest.load_metric("serve_ttft_ms").read({}, {}) is None


def test_batch_time_is_the_mean_data_batch_span_in_the_window():
    pt = {"spans": [("data.batch", 0, 2 * MS), ("data.batch", 5 * MS,
                                                 9 * MS),
                    ("data.batch", 20 * MS, 29 * MS)],
          "window": (1 * MS, 30 * MS), "hlo": {}}
    assert _read("train_batch_ms", pt) == pytest.approx((4 + 9) / 2)


@pytest.mark.parametrize("text,name", [
    ("serve.generate#call=3,batch=64#", "serve.generate"),
    ("serve.step", "serve.step"),
    ("data.batch#step=7#", "data.batch")])
def test_span_names_drop_their_arguments(text, name):
    assert progtrace.span_name(text) == name


def test_scope_is_a_whole_component_of_the_op_name():
    assert progtrace.in_scope("jit(train_step)/train.optimizer/mul",
                              "train.optimizer")
    assert not progtrace.in_scope(
        "jit(train_step)/transpose(jvp(train.forward))/dot_general",
        "train.optimizer")
    assert not progtrace.in_scope("jit(f)/train.optimizers/mul",
                                  "train.optimizer")


def _varint(n):
    out = b""
    while True:
        out += bytes([n & 0x7F | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _msg(*fields):
    """A serialized protobuf message of (number, int | bytes | str)."""
    out = b""
    for number, v in fields:
        if isinstance(v, int):
            out += _varint(number << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(number << 3 | 2) + _varint(len(v)) + v
    return out


def _hlo(names):
    """An HloProto of one computation whose instructions have these
    {name: op_name}."""
    insts = [(2, _msg((1, n), (2, "fusion"), (7, _msg((1, "mul"),
                                                       (2, op)))))
             for n, op in names.items()]
    return _msg((1, _msg((1, "jit_step"), (3, _msg((1, "main"), *insts)))))


def test_op_names_are_read_from_the_hlo_proto():
    names = {"fusion.1": "jit(step)/train.optimizer/mul",
             "fusion.2": "jit(step)/transpose(jvp(train.forward))/dot"}
    assert progtrace.op_names(_hlo(names)) == names


def test_hlo_protos_are_found_on_the_metadata_plane():
    hlo = _hlo({"fusion.1": "jit(step)/mul"})
    stat_meta = _msg((1, 7), (2, _msg((1, 7), (2, "Hlo Proto"))))
    event_meta = _msg((1, 42), (2, _msg((1, 42), (2, "jit_step(42)"),
                                        (5, _msg((1, 7), (6, hlo))))))
    other = _msg((1, 1), (2, "/device:TPU:0"), (4, event_meta))
    meta = _msg((1, 2), (2, "/host:metadata"), (5, stat_meta),
                (4, event_meta))
    space = _msg((1, other), (1, meta))
    assert progtrace.hlo_protos(space) == {"jit_step(42)": hlo}


def test_optimizer_time_per_step_execution():
    opt = "jit(train_step)/train.optimizer/mul"
    bwd = "jit(train_step)/transpose(jvp(train.forward))/dot_general"
    ops = [("fusion.1", 0, 6 * MS), ("fusion.2", 6 * MS, 8 * MS),
           ("fusion.1", 10 * MS, 16 * MS), ("fusion.2", 16 * MS, 19 * MS),
           ("fusion.2", 30 * MS, 31 * MS)]          # outside every run
    mods = [("jit_train_step(1)", 0, 9 * MS),
            ("jit_train_step(1)", 10 * MS, 19 * MS)]
    trace = {"host": [("bench.window", 0, 40 * MS)],
             "devices": [{"name": "/device:TPU:0", "modules": mods,
                          "ops": ops}]}
    pt = {"spans": [], "window": (0.0, 40 * MS),
          "hlo": {"jit_train_step(1)": _hlo({"fusion.1": bwd,
                                             "fusion.2": opt})}}
    assert _read("train_optimizer_ms", pt, trace) == pytest.approx(2.5)
    pt["hlo"] = {"jit_train_step(1)": _hlo({"fusion.1": bwd,
                                            "fusion.2": bwd})}
    assert _read("train_optimizer_ms", pt, trace) is None
    pt["hlo"] = {}
    assert _read("train_optimizer_ms", pt, trace) is None


def test_innermost_labels_where_a_gap_lies_among_the_inner_spans():
    spans = [("serve.generate", 1 * MS, 9 * MS),
             ("serve.step", 2 * MS, 4 * MS), ("serve.sync", 3 * MS, 4 * MS),
             ("serve.step", 5 * MS, 7 * MS), ("serve.sync", 6 * MS, 7 * MS)]
    segs = progtrace.innermost(spans, 0, 10 * MS)
    assert [(s / MS, e / MS, n) for s, e, n in segs] == [
        (0, 1, "unspanned"),
        (1, 2, "serve.generate/before serve.step"),
        (2, 3, "serve.step/before serve.sync"),
        (3, 4, "serve.sync"),
        (4, 5, "serve.generate/between"),
        (5, 6, "serve.step/before serve.sync"),
        (6, 7, "serve.sync"),
        (7, 9, "serve.generate/after serve.step"),
        (9, 10, "unspanned")]


def test_idle_goes_to_the_innermost_span_open_meanwhile():
    spans = [("serve.generate", 1 * MS, 9 * MS),
             ("serve.step", 2 * MS, 4 * MS), ("serve.sync", 3 * MS, 4 * MS)]
    ops = [("fusion", 3.5 * MS, 4 * MS), ("stack", 8 * MS, 8.5 * MS)]
    dev = {"name": "/device:TPU:0", "ops": ops, "modules": []}
    gaps = devtrace.gaps(dev, 0, 10 * MS)
    idle = progtrace.idle_by_innermost(spans, [gaps, gaps], 0, 10 * MS)
    assert idle == pytest.approx({
        "unspanned": 2e-3, "serve.generate/before serve.step": 1e-3,
        "serve.step/before serve.sync": 1e-3, "serve.sync": 0.5e-3,
        "serve.generate/after serve.step": 4.5e-3})
    assert sum(idle.values()) == pytest.approx(
        (10 * MS - devtrace.busy_ns(dev, 0, 10 * MS)) / 1e9)


def test_load_keeps_program_spans_and_scopes_from_a_profile(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from repro.obs import trace

    @jax.jit
    def scoped_step(x):
        with jax.named_scope("train.optimizer"):
            return jnp.sin(x) * 2.0

    x = jnp.ones((8, 8))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("bench.window"):
            with trace.span("serve.generate", call=1, batch=2):
                with trace.span("serve.step"):
                    scoped_step(x).block_until_ready()
            with TraceAnnotation("bench.input"):
                pass
    finally:
        jax.profiler.stop_trace()
    pt = progtrace.load(tmp_path)
    assert [s[0] for s in pt["spans"]] == ["serve.generate", "serve.step"]
    lo, hi = pt["window"]
    assert all(lo <= s <= e <= hi for _, s, e in pt["spans"])
    module, = [m for m in pt["hlo"] if m.startswith("jit_scoped_step")]
    names = progtrace.op_names(pt["hlo"][module]).values()
    assert any(progtrace.in_scope(n, "train.optimizer") for n in names)
