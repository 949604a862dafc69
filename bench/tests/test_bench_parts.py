"""The yardstick's parts on their own: the trace reducer, the kernel's
operation and byte counts, the peak table and the generators."""
import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts bench/ on the path)
import gen
import kernel_cost as kc
import peaks
import xplane


def _trace(ops, spans):
    return xplane.Trace(
        [xplane.Op("/device:TPU:0", n, m, s, d) for n, m, s, d in ops],
        [xplane.Span(n, s, d) for n, s, d in spans])


def test_reduce_busy_union_idle_and_gap_labels():
    ops = [("fused_seeds", "jit__absorb_jit(7)", 100, 100),     # 100-200
           ("fusion.1", "jit__absorb_jit(7)", 150, 100),        # 150-250
           ("segment_query", "jit__estimate_batch_jit", 600, 50),  # 600-650
           ("outside", "m", 2000, 10)]                          # clipped
    spans = [("window", 0, 1000), ("absorb", 50, 300),
             ("pump", 300, 400), ("launch", 500, 160)]
    red = xplane.reduce(_trace(ops, spans))
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s == pytest.approx(200e-9)        # 100-250 and 600-650
    assert red.ops["_absorb_jit/fused_seeds"] == pytest.approx(100e-9)
    assert xplane.op_seconds(red, "segment_query") == pytest.approx(50e-9)
    # gaps: 0-100 (absorb, mid 50), 250-600 (pump, mid 425),
    # 650-1000 (idle, mid 825)
    assert [(g, pytest.approx(s)) for g, s in red.gaps] == [
        ("pump", 350e-9), ("idle", 350e-9), ("absorb", 100e-9)]


def test_reduce_needs_the_window_span():
    with pytest.raises(ValueError):
        xplane.reduce(_trace([("a", "m", 0, 1)], []))


@pytest.mark.parametrize("thread", [False, True])
def test_load_reads_a_recorded_trace(tmp_path, thread):
    # the spans lie on the line of whichever thread ran the window, named
    # after that thread or the process ("python3", "python", ...)
    import threading
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()

    def window():
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.work"):
                for _ in range(3):
                    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    if thread:
        t = threading.Thread(target=window)
        t.start()
        t.join()
    else:
        window()
    jax.profiler.stop_trace()
    path = xplane.find_xplane(str(tmp_path))
    # on the CPU the "device" is the host's XLA client thread
    tr = xplane.load(path, device_plane=r"^/host:CPU$",
                     op_line=r"XLAPjRtCpuClient")
    assert {s.name for s in tr.spans} >= {"window", "work"}
    red = xplane.reduce(tr)
    assert red.ops and 0 < red.busy_s <= red.window_s
    assert all(label in ("work", "window", "idle") for label, _ in red.gaps)


def test_kernel_counts_by_hand():
    sc = kc.service_cost(1026, 641, 10, 68)
    assert sc["ops"] == 2 * 641 * 10 * 1026 * 68
    assert sc["bytes"] == (4 * 1026 * 68 + 2 * 4 * 1026 + 4 * 641 * 10 * 68
                           + 4 * 641)
    pk = peaks.peaks("TPU v5 lite")
    t, bound = kc.least_seconds(sc, pk)
    assert bound == "compute" and t == pytest.approx(sc["ops"] / 197e12)
    # one set of one centre against a few slots: reading the slab wins
    small = kc.service_cost(1026, 1, 1, 68)
    t, bound = kc.least_seconds(small, pk)
    assert bound == "memory" and t == pytest.approx(small["bytes"] / 819e9)


def test_peaks_raise_for_an_unknown_device():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        peaks.peaks("TPU v4")
    with pytest.raises(ValueError):
        peaks.peaks("cpu")


def test_generators_are_deterministic_by_seed():
    big = 2**33 + 12345
    p1 = gen.mixture_points(big, 256, 8, 4)
    p2 = gen.mixture_points(big, 256, 8, 4)
    p3 = gen.mixture_points(big + 1, 256, 8, 4)
    assert p1.shape == (256, 8) and p1.dtype == np.float32
    assert np.array_equal(np.asarray(p1), np.asarray(p2))
    assert not np.array_equal(np.asarray(p1), np.asarray(p3))


def test_bf16_rounding_is_on_the_bits():
    import ref_cluster
    x = np.array([1.0, 1.0 + 2.0**-8, 1.0 + 3 * 2.0**-9, -3.14159],
                 np.float32)
    got = np.asarray(ref_cluster.to_bf16(x))
    # ties go to even; bfloat16 keeps 8 bits of mantissa
    assert got.tolist() == [1.0, 1.0, 1.0 + 2.0**-7, -3.140625]
    assert ref_cluster.mismatch([1, 2, 3], [2, 3, 4]) == 2
