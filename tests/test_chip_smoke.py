"""``chip_smoke.py``'s phases (b)-(e) on the CPU at reduced width.

The chip runs them at smollm-135m's published width; here the same
code runs on the reduced config (kernels in interpret mode) so that a
broken path, argument or check fails in the test suite before it costs
chip time. The device check (a) is what refuses the CPU, so it is
tested for exactly that.
"""
import jax
import pytest

import chip_smoke
from repro.configs.registry import get_config
from repro.data.tasks import arithmetic_suite

SEED = 3


@pytest.fixture(scope="module")
def cfg():
    return get_config("smollm-135m", reduced=True)


@pytest.fixture(scope="module")
def tasks():
    return arithmetic_suite(chip_smoke.N_TASKS, seed=SEED)


def test_device_check_refuses_cpu(capsys):
    with pytest.raises(chip_smoke.PhaseFailed, match="no TPU"):
        chip_smoke.device_check(1)
    assert "platform=cpu" in capsys.readouterr().out


def test_main_exits_nonzero_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out


def test_kernels_match_oracles(cfg):
    errs = chip_smoke.phase_kernels(cfg, interpret=True, seed=SEED)
    assert set(errs) == {"paged_decode", "chunked_prefill",
                         "paged_decode_quant", "fused_swiglu"}
    for err, tol in errs.values():
        assert 0.0 <= err <= tol


def test_paged_path_matches_dense_generate(cfg, tasks):
    probe, _ = chip_smoke.build_fleet(cfg, SEED)
    out = chip_smoke.phase_correctness(cfg, probe.params,
                                       tasks[:chip_smoke.N_PROMPTS])
    assert out["err"] <= out["tol"]
    assert out["rows"] == chip_smoke.N_PROMPTS


def test_serving_twice_then_with_kernels(cfg, tasks):
    probe, ensemble = chip_smoke.build_fleet(cfg, SEED)
    served = chip_smoke.phase_serving(probe, ensemble, SEED, tasks)
    assert len(served["hashes"]) == len(tasks)
    res = served["result"]
    assert sorted(set(int(m) for m in res.modes)) == [0, 1, 2]
    assert res.step.prefill_chunks > 0
    agree = chip_smoke.phase_pallas_serving(served, SEED, tasks)
    # off the chip the kernels dispatch to their jnp oracles, which
    # the serving path's bits are built on
    assert jax.default_backend() != "tpu"
    assert agree["hashes"] == len(tasks)


def test_mesh_leg_against_one_device(cfg, tasks):
    # the --chips 4 code path on a one-device mesh: the CPU has one
    # device here, and the sharded runner must still match the plain one
    out = chip_smoke.phase_mesh(SEED, tasks, ((cfg, 1, 1),))
    assert out == {(1, 1): {"modes": 0, "final_answers": 0,
                            "record_hashes": 0, "chain_head": 0,
                            "logit_diff": 0.0}}
