"""The per-layer readers that find device ops by the program's scope names
(``bench/scopes.py``), on hand-made traces whose op names are HLO text as a
TPU trace gives it: the scopes ride as frontend attributes."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace_reduce as tr  # noqa: E402
from bench.run import reader  # noqa: E402

NEW = ("interp_busy_pct", "gradient_busy_pct", "pcg_busy_pct", "linesearch_busy_pct",
       "unscoped_busy_pct")


def op(name, start, end, attrs=None, opcode="fusion"):
    """An op as a TPU trace names it: its HLO text, with the frontend
    attributes the program's scopes set."""
    text = f"%{name} = f32[1,128,128,128]{{3,2,1,0}} {opcode}(f32[1,128,128,128] %p)"
    if attrs:
        text += ", frontend_attributes={" + ",".join(
            f'{k}="{v}"' for k, v in sorted(attrs.items())) + "}"
    return tr.Op(text, start * 1e-9, end * 1e-9)


def step_trace():
    """One Newton step on one chip, in ns: PCG [0, 70) in a while loop,
    the gradient [100, 140), the line search [140, 175), the update
    [175, 178), then unnamed ops [180, 192); busy 160."""
    pcg, grad, ls = {"gn": "gn.pcg"}, {"gn": "gn.gradient"}, {"gn": "gn.line_search"}
    return [
        op("while.3", 0, 70, pcg, opcode="while"),
        op("tricubic_apply_pallas_padded.37", 0, 50,
           dict(pcg, interp="interp.apply", objective="objective.gn_hessian_matvec"),
           opcode="custom-call"),
        op("fusion.1376", 50, 60, pcg),
        op("fusion.606", 60, 70, dict(pcg, fft="fft.inv")),
        op("tricubic_displace_pallas_padded_many.11", 100, 130,
           dict(grad, interp="interp.displace"), opcode="custom-call"),
        op("select_maximum_fusion.35", 130, 140, dict(grad, interp="interp.plan")),
        op("tricubic_apply_pallas_padded.38", 140, 170, dict(ls, interp="interp.apply"),
           opcode="custom-call"),
        op("fusion.607", 170, 175, dict(ls, fft="fft.fwd")),
        op("fusion.9", 175, 178, {"gn": "gn.update"}),
        op("copy.1", 180, 190, opcode="copy"),
        op("convolution_add_fusion.2", 190, 192),
    ]


def read(name, ops_by_device):
    return reader(ROOT, name).read({"trace": {"ops": ops_by_device, "peaks": None}})


def test_each_reader_reads_its_scope_share():
    ops = step_trace()
    assert tr.busy(ops) == pytest.approx(160e-9)
    got = {m: read(m, {"/device:TPU:0": ops}) for m in NEW}
    assert got == pytest.approx({
        "pcg_busy_pct": 100 * 70 / 160, "gradient_busy_pct": 100 * 40 / 160,
        "linesearch_busy_pct": 100 * 35 / 160, "unscoped_busy_pct": 100 * 12 / 160,
        "interp_busy_pct": 100 * 120 / 160})
    # the phases and the unnamed ops make the whole but for gn.update
    phases = sum(got[m] for m in ("gradient_busy_pct", "pcg_busy_pct",
                                  "linesearch_busy_pct", "unscoped_busy_pct"))
    assert phases == pytest.approx(100 * (160 - 3) / 160)
    # interpolation cuts across the phases
    assert got["interp_busy_pct"] > max(got[m] for m in NEW if m != "interp_busy_pct")


def test_readers_average_over_the_chips_that_name_the_scope():
    ops = step_trace()
    half = [o for o in ops if "gn.pcg" not in o.name]  # a chip whose step has no PCG
    got = read("pcg_busy_pct", {"/device:TPU:0": ops, "/device:TPU:1": half})
    assert got == pytest.approx(100 * 70 / 160)  # the second chip names no gn.pcg
    got = read("interp_busy_pct", {"/device:TPU:0": ops, "/device:TPU:1": half})
    assert got == pytest.approx(0.5 * (100 * 120 / 160 + 100 * 70 / 90))


def test_a_trace_without_the_scopes_reads_none():
    """The same ops as a program that sets no scope names: every new reader
    reads None, and the kernel found by its name still reads."""
    bare = [tr.Op(o.name.split(", frontend_attributes")[0], o.start, o.end)
            for o in step_trace()]
    for m in NEW:
        assert read(m, {"/device:TPU:0": bare}) is None, m
        assert read(m, {"/device:TPU:0": step_trace()}) is not None, m
        assert reader(ROOT, m).read({"trace": None}) is None, m
    assert read("tricubic_busy_pct", {"/device:TPU:0": bare}) == pytest.approx(
        100 * 110 / 160)


def test_scope_names_match_whole():
    """A scope matches only as an attribute's whole value: not an HLO op
    named ``fft.12``, not a longer scope name, not the name in other text."""
    ops = [op("fft.12", 0, 10, opcode="fft"),
           op("fusion.1", 10, 20, {"gn": "gn.pcg_extra"}),
           tr.Op('%fusion.2 = f32[] fusion(), calls=%gn.pcg', 20e-9, 30e-9)]
    for m in ("interp_busy_pct", "pcg_busy_pct", "unscoped_busy_pct"):
        assert read(m, {"/device:TPU:0": ops}) is None, m


def test_ops_that_lose_their_names_count_as_unscoped():
    """The TPU's FFT expansion rewrites each transform into ops that carry
    none of the program's names: they leave their phase for the unscoped
    share, and the phases and the unscoped ops still make the whole."""
    ops = [tr.Op(o.name.split(", frontend_attributes")[0], o.start, o.end)
           if "fft=" in o.name else o for o in step_trace()]
    got = {m: read(m, {"/device:TPU:0": ops}) for m in NEW}
    assert got == pytest.approx({
        "pcg_busy_pct": 100 * 60 / 160, "gradient_busy_pct": 100 * 40 / 160,
        "linesearch_busy_pct": 100 * 30 / 160, "unscoped_busy_pct": 100 * 27 / 160,
        "interp_busy_pct": 100 * 120 / 160})
