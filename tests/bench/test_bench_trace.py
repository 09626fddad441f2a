"""The reduction from a profiler trace to the benchmark's device numbers."""

from __future__ import annotations

import pytest

import benchtest_util  # noqa: F401  (puts the checkout on sys.path)
from bench import devtrace

NS = 1e-9


def _trace(devices: dict) -> dict:
    return {
        "host": [("bench_window", 0, 1000), ("solve", 0, 400), ("solve", 500, 400),
                 ("select", 0, 5000)],
        "devices": devices,
        "layout": [],
    }


def test_busy_kernel_and_gaps_by_host_span():
    dev = {
        "ops": [("fusion", 100, 50), ("custom-call", 140, 60), ("copy", 600, 10),
                ("before", -50, 20)],
        "modules": [("jit__placement_sweep_batch_padded(7)", 100, 100),
                    ("jit_other(3)", 600, 10)],
    }
    out = devtrace.reduce(_trace({"/device:TPU:0": dev}))
    assert out["window_s"] == pytest.approx(1000 * NS)
    # [100, 200) and [600, 610): overlapping ops count once.
    assert out["busy_s"] == pytest.approx(110 * NS)
    # Only the sweep's program is the kernel.
    assert out["kernel_s"] == pytest.approx(100 * NS)
    assert out["kernel_total_s"] == pytest.approx(100 * NS)
    assert out["devices"] == 1 and out["devices_with_kernel"] == 1
    # Gaps are named by the innermost benchmark span covering their middle
    # ("select" spans them all, "solve" two of them); the window span
    # itself never names a gap.
    assert [(n, round(t / NS)) for n, t in out["idle_gaps"]] == [
        ("select", 400), ("solve", 390), ("solve", 100)]
    assert out["device_ops"][0] == ["custom-call", pytest.approx(60 * NS)]
    assert "before" not in {n for n, _ in out["device_ops"]}


def test_a_gap_outside_every_span_belongs_to_the_harness():
    tr = _trace({"/device:TPU:0": {"ops": [("x", 300, 600)], "modules": []}})
    tr["host"] = [h for h in tr["host"] if h[0] != "select"]
    out = devtrace.reduce(tr)
    assert [(n, round(t / NS)) for n, t in out["idle_gaps"]] == [
        ("solve", 300), ("harness", 100)]


def test_devices_are_averaged_and_summed():
    busy = {"ops": [("x", 0, 500)], "modules": [("jit_sweep(1)", 0, 500)]}
    idle = {"ops": [], "modules": []}
    out = devtrace.reduce(_trace({"/device:TPU:0": busy, "/device:TPU:1": idle}))
    assert out["busy_s"] == pytest.approx(250 * NS)
    assert out["kernel_s"] == pytest.approx(250 * NS)
    assert out["kernel_total_s"] == pytest.approx(500 * NS)
    assert out["devices_with_kernel"] == 1


def test_a_trace_without_the_window_is_refused():
    tr = _trace({})
    tr["host"] = [h for h in tr["host"] if h[0] != "bench_window"]
    with pytest.raises(ValueError):
        devtrace.reduce(tr)


def test_load_reads_host_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones(128)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench_window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("solve"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = devtrace.load(devtrace.find(str(tmp_path)), {"bench_window", "solve"})
    names = [n for n, _, _ in tr["host"]]
    assert names.count("bench_window") == 1 and names.count("solve") == 3
    out = devtrace.reduce(tr)
    assert out["window_s"] > 0


def test_op_names_drop_their_hlo_text():
    assert devtrace.short("%copy.1 = s32[1,64]{1,0} copy(s32[1,64]{1,0} %args_0_.1)") == "copy.1"
    assert devtrace.short("fusion") == "fusion"
