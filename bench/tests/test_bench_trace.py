"""The trace reduction, the interpolation work function and the peaks table
of the benchmark under ``bench/``."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace_reduce as tr  # noqa: E402
from bench import work  # noqa: E402

KERNEL = r"_kernel_planned|_kernel_displace|tricubic"


def event(meta, start_ns, dur_ns, long_name=None):
    stat = f' stats {{ metadata_id: 1 str_value: "{long_name}" }}' if long_name else ""
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000} "
            f"duration_ps: {dur_ns * 1000}{stat} }}")


def tpu_trace():
    """Two chips of a TPU-like trace: a kernel overlapping a fusion, an
    all-to-all partly hidden behind a fusion, an exposed permute."""
    names = {1: "_kernel_planned", 2: "fusion.3", 3: "all-to-all.4",
             4: "collective-permute-start.5"}
    call = "%custom-call.1 = f32[2,8,16,128]{3,2,1,0} custom-call(f32[2,27,40,256] %p)"
    ops = " ".join([event(1, 0, 100, call), event(2, 50, 100), event(3, 200, 100),
                    event(2, 250, 10), event(4, 400, 50)])
    meta = " ".join(f'event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}'
                    for k, v in names.items())
    planes = [
        f'planes {{ id: {i + 1} name: "/device:TPU:{i}" '
        f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {ops} }} {meta} '
        f'stat_metadata {{ key: 1 value {{ id: 1 name: "long_name" }} }} }}'
        for i in range(2)
    ]
    planes.append('planes { id: 9 name: "/host:CPU" lines { id: 1 name: "python" '
                  'timestamp_ns: 0 events { metadata_id: 1 offset_ps: 140000 '
                  'duration_ps: 100000 } } event_metadata { key: 1 value { id: 1 '
                  'name: "PjitFunction(step)" } } }')
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto("\n".join(planes))


def test_reduction_of_a_tpu_like_trace():
    prof = tpu_trace()
    ops = tr.device_ops(prof)
    assert sorted(ops) == ["/device:TPU:0", "/device:TPU:1"]
    dev = ops["/device:TPU:0"]
    ns = 1e-9
    assert tr.busy(dev) == pytest.approx(300 * ns)  # [0,150) + [200,300) + [400,450)
    assert tr.busy(tr.matching(dev, KERNEL)) == pytest.approx(100 * ns)
    assert tr.exposed(dev, tr.A2A) == pytest.approx(90 * ns)
    assert tr.exposed(dev, tr.PERMUTE) == pytest.approx(50 * ns)
    top = dict(tr.top_ops(dev))
    assert top["fusion"] == pytest.approx(110 * ns)
    gaps = tr.idle_gaps(dev, tr.host_spans(prof))
    assert gaps[0] == ["no host event", pytest.approx(100 * ns)]
    assert gaps[1] == ["PjitFunction(step)", pytest.approx(50 * ns)]


def test_reduction_of_a_recorded_cpu_trace():
    ops = tr.device_ops(tr.load(str(ROOT / "bench" / "tests" / "data" / "cpu.xplane.pb")))
    assert list(ops) == ["/host:CPU"]
    names = {o.name for o in ops["/host:CPU"]}
    assert names == {"wrapped_sine", "dot_general.1"}
    assert 0 < tr.busy(ops["/host:CPU"]) < 1e-3
    assert not tr.matching(ops["/host:CPU"], tr.A2A)


def test_interpolation_work_depends_on_points_fields_and_dtypes_only():
    flops, nbytes = work.interp_work(points=128**3, fields=1, field_bytes=4)
    assert nbytes == 128**3 * (4 + 4 + 12)
    assert flops == 128**3 * (48 + 168)
    # the same call, whatever the kernel's padding, tiling or plan layout
    a = work.call_work("%custom-call.1 = f32[3,128,128,128]{3,2,1,0} "
                       "custom-call(f32[3,147,152,256] %pad, s32[3,128,128,128] %ib)")
    b = work.call_work("%fusion.9 = f32[3,128,128,128]{3,2,1,0} fusion(f32[3,4,128,128,128] %w)")
    assert a == b == work.interp_work(128**3, 3, 4)
    assert work.call_work("%x = bf16[2,64,64,64] custom-call()") == work.interp_work(64**3, 2, 2)
    assert work.call_work("%x = f32[] constant(1)") is None
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = work.least_time(flops, nbytes, peaks)
    assert bound == "hbm" and t == pytest.approx(nbytes / 819e9)


def test_tricubic_roofline_reads_the_trace():
    sys.path.insert(0, str(ROOT / "bench" / "metrics"))
    import tricubic_busy_pct
    import tricubic_roofline_pct

    prof = tpu_trace()
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())["TPU v5 lite"]
    run = {"trace": {"ops": tr.device_ops(prof), "peaks": peaks, "window_s": 1e-6}}
    least = work.least_time(*work.interp_work(8 * 16 * 128, 2, 4), peaks)[0]
    assert tricubic_roofline_pct.read(run) == pytest.approx(100 * least / 100e-9)
    assert tricubic_busy_pct.read(run) == pytest.approx(100 * 100 / 300)
    assert tricubic_roofline_pct.read({"trace": None}) is None


def test_peaks_reject_an_unknown_device():
    from bench.run import peaks_for

    assert peaks_for(ROOT, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit, match="no published peaks"):
        peaks_for(ROOT, "TPU v99")


def test_harness_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", cell,
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120, cwd=ROOT,
    )
    assert proc.returncode == 2
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr
