"""Benchmark plumbing that must not hide a failure or a device."""
from types import SimpleNamespace

import pytest

from benchmarks import roofline
from benchmarks.run import run_sections


def _section(fail: bool):
    def run(verbose=True):
        if fail:
            raise RuntimeError("section broke")
    return SimpleNamespace(run=run, main=lambda: "ok,1.0,")


@pytest.mark.parametrize("fails,code", [((), 0), ((1,), 1),
                                        ((0, 1), 1)])
def test_run_sections_exit_code(fails, code, capsys):
    sections = [(f"s{i} title", _section(i in fails)) for i in range(2)]
    assert run_sections(sections) == code
    out = capsys.readouterr()
    # every section is reported, failed or not
    assert out.out.count("== s") == 2
    assert ("FAILED sections" in out.err) == bool(fails)


def test_peaks_are_published_v5e_figures():
    pk = roofline.peaks("TPU v5 lite")
    assert pk == {"flops_bf16": 197e12, "hbm_bytes_s": 819e9,
                  "ici_bytes_s": 1600e9 / 8}


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.peaks("cpu")
