"""The trace reduction on hand-built traces, and on one recorded here."""

from __future__ import annotations

import pytest

from bench.lib import xtrace
from bench.lib.xtrace import Event, Trace


BITS = (r'^%_bin_bin_(bin|bin_pull|full)\.\d+ = .*tpu_custom_call',)
VALS = (r'^%_bin_full_full\.\d+ = .*tpu_custom_call',)


def _hlo(name, result, rest=""):
    return f"%{name} = {result} {rest}"


def _trace():
    ms = 1e6
    kernel = 'custom-call(u32[8,128]), custom_call_target="tpu_custom_call"'
    dev = [Event(_hlo("while.6", "(u32[4], s32[])", "while(...)"),
                 10 * ms, 35 * ms),                 # encloses its body
           Event(_hlo("fusion.1", "f32[8,32]{1,0:T(8,128)}", "fusion(..)"),
                 10 * ms, 20 * ms),
           Event(_hlo("_bin_bin_bin_pull.8", "s32[1,1,256]", kernel),
                 30 * ms, 10 * ms),
           Event(_hlo("custom-call.3", "u32[8]",
                      'custom_call_target="ConcatBitcast"'), 40 * ms, 5 * ms),
           Event(_hlo("_bin_full_full.13", "f32[1,1,256]", kernel),
                 80 * ms, 10 * ms),
           Event(_hlo("fusion.2", "f32[8,32]{1,0:T(8,128)}", "fusion(..)"),
                 150 * ms, 100 * ms)]               # runs past the window
    host = [Event(xtrace.WINDOW, 0, 200 * ms),
            Event("bench:bfs_call", 0, 200 * ms),
            Event("lower_sharding_computation", 46 * ms, 30 * ms),
            Event("PjitFunction(while)", 90 * ms, 58 * ms)]
    return Trace(dev, host, (0.0, 200 * ms))


def test_busy_is_the_union_clipped_to_the_window():
    tr = _trace()
    # [10,45] + [80,90] + [150,200] ms
    assert tr.busy_s() == pytest.approx(0.095)
    assert tr.window_s == pytest.approx(0.2)
    assert tr.idle_share() == pytest.approx(1 - 0.095 / 0.2)


def test_kernel_time_matches_patterns_in_name_or_metadata():
    tr = _trace()
    assert tr.kernel_s(BITS) == pytest.approx(0.010)
    assert tr.kernel_s(VALS) == pytest.approx(0.010)
    pallas = (r'custom_call_target="tpu_custom_call"',)
    assert tr.kernel_s(pallas) == pytest.approx(0.020)   # not ConcatBitcast
    assert tr.kernel_s((r"no_such_kernel",)) == 0.0


def test_metric_files_match_the_kernels_as_a_tpu_trace_names_them():
    from bench.lib import harness
    tr = _trace()
    ctx = harness.MetricCtx(window_s=0.2, trace=tr, peaks={"hbm_bw": 1e9},
                            facts={"bits_bytes": 5e6, "vals_bytes": 2e6})
    assert harness.load_metric("bits_kernel_roofline").read(ctx) == \
        pytest.approx(50.0)
    assert harness.load_metric("vals_kernel_hbm_pct").read(ctx) == \
        pytest.approx(20.0)
    # busy 95 ms, of which 20 ms in the two Pallas kernels
    assert harness.load_metric("wrapper_share_pct").read(ctx) == \
        pytest.approx(75 / 95 * 100)


def test_idle_gaps_are_named_by_the_innermost_host_activity():
    gaps = dict(_trace().idle_gaps())
    # [0,10]: only the call covers it; [45,80]: lowering covers 30 of 35;
    # [90,150]: the jitted call covers 58 of 60
    assert gaps["bench:bfs_call"] == pytest.approx(0.010)
    assert gaps["lower_sharding_computation"] == pytest.approx(0.035)
    assert gaps["PjitFunction(while)"] == pytest.approx(0.060)
    assert sum(gaps.values()) == pytest.approx(0.2 - 0.095)


def test_top_ops_count_leaves_grouped_by_name_and_type():
    top = dict(_trace().top_ops())
    assert top["fusion f32[8,32]"] == pytest.approx(0.020 + 0.050)
    assert top["_bin_bin_bin_pull s32[1,1,256]"] == pytest.approx(0.010)
    assert "while" not in top            # it encloses the others


def test_busy_is_averaged_over_the_cells_devices():
    ms = 1e6
    dev = [Event("a", 0, 50 * ms, device=0), Event("b", 0, 10 * ms, device=1)]
    tr = Trace(dev, [], (0.0, 100 * ms), n_devices=2)
    assert tr.busy_s() == pytest.approx(0.030)


def test_load_reads_a_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(xtrace.WINDOW):
        for _ in range(3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = xtrace.load(str(tmp_path))
    assert tr.window_s > 0
    assert tr.device_events, "the CPU's executed operations"
    assert 0 < tr.busy_s() <= tr.window_s
    assert "PLANE" in xtrace.describe(str(tmp_path))
