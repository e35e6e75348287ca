"""The trace reduction, on synthetic intervals and on a trace recorded here
(CPU: host threads only, so no device busy time and no share read)."""
import os
import sys
import threading

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import trace as tr  # noqa: E402


def test_union_merges_and_clips():
    got = tr.union([(5, 8), (0, 2), (1, 3), (7, 12), (20, 30)], 1, 25)
    assert got == [(1, 3), (5, 12), (20, 25)]


def test_gaps_complement_busy():
    busy = [(1, 3), (5, 12)]
    assert tr.gaps(busy, 0, 15) == [(0, 1), (3, 5), (12, 15)]
    assert tr.gaps([], 0, 4) == [(0, 4)]
    assert tr.gaps([(0, 4)], 0, 4) == []


def _synthetic():
    t = tr.Trace()
    # window 0..100 ns; device busy 10..40 and 60..70 (ops overlap)
    t.host.append((0, 0, 100, "bench.window"))
    t.ops.append([(10, 30), (20, 40), (60, 70), (150, 160)])
    t.modules += [(10, 40, "jit__lambda"), (60, 70, "jit_apply_batch"),
                  (150, 160, "jit__lambda")]
    # the updater submits across the long gap 40..60; all clients wait
    t.host += [(1, 38, 62, "bench.updater.submit"),
               (2, 0, 100, "bench.client.wait"),
               (3, 41, 58, "PjitFunction(_dirty_stats)"),
               (3, 71, 72, "PjitFunction(concatenate)")]
    return t


def test_reduce_synthetic():
    red = tr.reduce(_synthetic())
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s == pytest.approx(40e-9)
    assert red.programs == pytest.approx({"jit__lambda": 30e-9,
                                          "jit_apply_batch": 10e-9})
    labels = dict(red.idle_by_label)
    assert labels["client.wait+updater.submit | "
                  "PjitFunction(_dirty_stats)"] == pytest.approx(20e-9)
    assert sum(labels.values()) == pytest.approx(60e-9)
    assert red.idle_by_label[0][1] >= red.idle_by_label[-1][1]


def test_reduce_labels_only_the_longest_gaps():
    t = _synthetic()
    red = tr.reduce(t, labelled=1)
    labels = dict(red.idle_by_label)
    assert labels["shorter gaps"] == pytest.approx(30e-9)


def test_reduce_needs_the_window_span():
    t = _synthetic()
    t.host = [h for h in t.host if h[3] != tr.WINDOW_SPAN]
    with pytest.raises(ValueError):
        tr.reduce(t)


def _plane(name, lines):
    from types import SimpleNamespace as NS
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=n, start_ns=s, duration_ns=d)
                            for n, s, d in evs])
        for ln, evs in lines.items()])


def test_only_planes_with_device_ops_are_devices():
    """A TPU host's trace also has device planes without device work (the
    Megascale plane); they are not devices, or busy time would halve."""
    from types import SimpleNamespace as NS
    pd = NS(planes=[
        _plane("/host:CPU", {"python3": [("bench.window", 0, 100),
                                         ("PjitFunction(f)", 5, 2),
                                         ("other", 1, 1)]}),
        _plane("/device:TPU:0", {
            tr.OPS_LINE: [("%fusion.1", 10, 30), ("%while.2", 40, 50)],
            tr.MODULES_LINE: [("jit_apply_batch(123)", 10, 80)],
            "Async XLA Ops": [("%copy-start.4", 12, 70)]}),
        _plane("/device:CUSTOM:Megascale Trace", {}),
    ])
    t = tr.from_profile(pd)
    assert t.ops == [[(10, 40), (40, 90)]]
    assert t.modules == [(10, 90, "jit_apply_batch")]
    assert [h[3] for h in t.host] == ["bench.window", "PjitFunction(f)"]
    red = tr.reduce(t)
    assert red.busy_s == pytest.approx(80e-9)
    assert red.programs == pytest.approx({"jit_apply_batch": 80e-9})


def test_program_name_drops_the_id():
    assert tr.program_name("jit_apply_batch(42)") == "jit_apply_batch"
    assert tr.program_name("jit__lambda") == "jit__lambda"


def test_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        return (x @ x).sum()

    x = jnp.ones((64, 64))
    step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        def client():
            with jax.profiler.TraceAnnotation("bench.client.wait"):
                step(x).block_until_ready()

        with jax.profiler.TraceAnnotation("bench.window"):
            th = threading.Thread(target=client)
            th.start()
            th.join(timeout=60)
            step(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert not th.is_alive()
    t = tr.load(str(tmp_path))
    names = {h[3] for h in t.host}
    assert {"bench.window", "bench.client.wait"} <= names
    assert any(n.startswith("PjitFunction(step") for n in names)
    # the client's annotation and the window's sit on different threads
    threads = {h[0] for h in t.host if h[3].startswith("bench.")}
    assert len(threads) == 2
    red = tr.reduce(t)
    assert red.window_s > 0
    # the CPU backend has no device plane: nothing is busy, nothing is read
    assert t.ops == [] and red.busy_s == 0.0 and red.programs == {}
