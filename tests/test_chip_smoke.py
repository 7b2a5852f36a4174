"""The chip smoke script's phases, run on the CPU at reduced widths.

``chip_smoke.py`` runs at smollm-135m's published widths on a TPU; here
its serving phase (with the logits comparison against the float32
reference) runs at ``reduce(smollm_135m)``, its pipeline phase runs the
Pallas kernels in interpret mode, and ``main`` must refuse the CPU.
"""
import dataclasses
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import chip_smoke  # noqa: E402
import repro.configs as configs  # noqa: E402
from repro.configs.base import reduce  # noqa: E402
from repro.launch.serve import Server, drain  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.models.reference import LOGIT_TOL  # noqa: E402

TINY = chip_smoke.Traffic(requests=6, prompt_min=8, prompt_max=24,
                          shared_prefix=8, gen=4, stagger=1, batch=2,
                          page_size=4, checked=3)


@pytest.fixture(scope="module")
def cfg():
    return reduce(configs.get(chip_smoke.ARCH))


def test_serve_phase_matches_reference(cfg):
    s = chip_smoke.serve_phase(cfg, TINY)
    assert s["requests"] == TINY.requests
    assert s["tokens"] == TINY.requests * TINY.gen
    assert s["prefill_tokens_skipped"] > 0       # the shared prefix hit
    assert len(s["logit_err"]) == TINY.checked
    assert max(s["logit_err"].values()) <= LOGIT_TOL["float32"]


def test_float32_tolerance_rejects_a_bfloat16_step(cfg):
    """The comparison is tight enough to notice a float32 config whose
    steps ran in bfloat16."""
    low = dataclasses.replace(cfg, param_dtype="bfloat16",
                              compute_dtype="bfloat16")
    params, _ = lm.init_params(low, jax.random.PRNGKey(0))
    server = Server(low, params, batch=TINY.batch, max_len=TINY.max_len,
                    page_size=TINY.page_size)
    done = drain(server, chip_smoke.make_requests(low, TINY)[:2])
    err = chip_smoke.check_against_reference(server, done[0])
    assert err > 10 * LOGIT_TOL["float32"], err
    assert err <= LOGIT_TOL["bfloat16"], err


def test_pipeline_phase_interpret_matches_host():
    p = chip_smoke.pipeline_phase()
    assert p["shape"] == (8, 32)
    # every accelerator stage was lowered and found interpreted
    assert p["kernels"] == {"conv": False, "pool": False, "fc": False}


def test_main_refuses_the_cpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert "cpu" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out
