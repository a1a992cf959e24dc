"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and its
kernel phase rehearses at small sizes against the ``kernels/ref.py``
oracles (the kernels interpret here), so the script's own checks are
exercised before any chip run."""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

_SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_exits_nonzero_without_tpu(chip_smoke, capsys, argv):
    assert chip_smoke.main(argv) != 0
    out = capsys.readouterr().out
    assert "platform=cpu" in out
    assert '"ok"' not in out


def test_kernel_phase_matches_oracles(chip_smoke):
    # arenas of more than one 8192-element pack block, ragged leaves
    out = chip_smoke.kernel_phase(
        jax.random.key(0),
        pack_cases=((jnp.float32, 9000, (100, 8200)),
                    (jnp.bfloat16, 9000, (17, 8300))),
        combine_n=5000, combine_ragged=3001, quant_rows=70, topk_n=5000,
        prefix_shape=(300, 8), rglru_shape=(300, 8), wkv_shape=(1, 70, 64))
    for name in ("pack_combine", "combine", "quant_combine",
                 "topk_accumulate", "prefix_sum", "rglru_scan",
                 "rwkv6_recurrence"):
        assert f"{name}[" in out
