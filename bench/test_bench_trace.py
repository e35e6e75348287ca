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


def test_one_device_reduces_as_before():
    """A one-device trace without op names: the readings the one-chip cells
    report are those of the single device, and no collective is read."""
    red = tr.reduce(_synthetic())
    assert red.idle_by_device == [red.idle_by_label]
    assert red.collectives == tr.Collectives(0.0, 0.0, {})
    assert sum(red.programs.values()) == pytest.approx(red.busy_s)


def _four_devices():
    """Window 0..100 ns on four devices that run one SPMD program 0..60.
    Device d's compute ops and collectives (``-start``/``-done`` halves and
    a synchronous all-reduce); the exposed part is what no other op covers:
    d0: all-reduce 20..30 inside compute 10..40 -> hidden; a permute done
        50..70 of which compute covers 50..55 -> 15 exposed;
    d1: all-gather-start 0..5 under compute 0..10, done 30..45 with
        compute 40..50 -> 10 exposed (30..40);
    d2: a collective 95..120 clipped to the window -> 5 exposed;
    d3: no collective, the same program.
    Device 0's loop (``while``) spans its collectives and covers none, and
    its permute is named as the chip names it, with the instruction."""
    t = tr.Trace()
    t.host.append((0, 0, 100, "bench.window"))
    devs = [
        [("while.9 = (s32[]) while(s32[] %x), body=%body", 5, 75),
         ("fusion.1", 10, 40), ("%all-reduce.3", 20, 30),
         ("copy.2", 45, 55),
         ("collective-permute-done.4 = s8[16384,65536]{1,0} "
          "collective-permute-done((s8[16384,65536]{1,0}) %x)", 50, 70)],
        [("fusion.1", 0, 10), ("all-gather-start.2", 0, 5),
         ("all-gather-done.2", 30, 45), ("fusion.5", 40, 50)],
        [("fusion.1", 0, 60), ("all-reduce-start.9", 95, 120)],
        [("fusion.1", 0, 60)],
    ]
    for ops in devs:
        t.ops.append([(s, e) for _, s, e in ops])
        t.op_names.append([n for n, _, _ in ops])
        t.modules.append((0, 60, "jit__bfs_body"))
    return t


def test_exposed_collectives_over_four_devices():
    red = tr.reduce(_four_devices())
    c = red.collectives
    # per device: collective union 10+20, 5+15, 5, 0; exposed 15, 10, 5, 0
    assert c.seconds == pytest.approx((30 + 20 + 5 + 0) / 4 * 1e-9)
    assert c.exposed_s == pytest.approx((15 + 10 + 5 + 0) / 4 * 1e-9)
    assert c.ops == pytest.approx({
        "all-reduce": 10 / 4 * 1e-9, "collective-permute-done": 20 / 4 * 1e-9,
        "all-gather-start": 5 / 4 * 1e-9, "all-gather-done": 15 / 4 * 1e-9,
        "all-reduce-start": 5 / 4 * 1e-9})
    # one SPMD program counts its time on one device
    assert red.programs == pytest.approx({"jit__bfs_body": 60e-9})
    assert len(red.idle_by_device) == 4
    assert red.idle_by_device[0] == red.idle_by_label
    # device 3 is idle 60..100, device 0 (its loop included) 0..5, 75..100
    assert sum(v for _, v in red.idle_by_device[3]) == pytest.approx(40e-9)
    assert sum(v for _, v in red.idle_by_device[0]) == pytest.approx(30e-9)


def test_collective_names():
    assert tr.collective_name("%collective-permute-done.7") == \
        "collective-permute-done"
    assert tr.collective_name("all-reduce.3") == "all-reduce"
    assert tr.collective_name("reduce-scatter") == "reduce-scatter"
    assert tr.collective_name("fusion.all-reduce.1") == ""
    assert tr.collective_name("all-reducer.2") == ""
    assert tr.collective_name("copy-start.1") == ""
    assert tr.collective_name(
        "collective-permute-done.3 = s32[128,512]{1,0} collective-permute-"
        "done((s32[128,512]{1,0}) %collective-permute-start.3)") == \
        "collective-permute-done"
    assert tr.collective_name("while.2 = (s32[]) while(s32[] %x)") == ""


def test_covered_by_merged_intervals():
    assert tr.covered([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert tr.covered([(0, 10)], []) == 0
    assert tr.covered([(0, 10)], [(0, 3), (4, 6), (9, 12)]) == 6


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
    assert t.op_names == [["%fusion.1", "%while.2"]]
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
