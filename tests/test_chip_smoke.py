"""``chip_smoke.py`` on the CPU: it must refuse to run without a TPU, and its
greedy check must pass equal token streams and fail a parting that is not
a bf16 near-tie. (The chip path itself only runs on a TPU.)"""
import importlib.util
import pathlib

import jax
import numpy as np
import pytest

from repro.launch import cli_args
from repro.serving import ServeRequest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu(chip_smoke, monkeypatch, capsys):
    # keep the suite's persistent compile cache off
    monkeypatch.setattr(cli_args, "enable_compile_cache", lambda: "off")
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert '"ok"' not in out and "no TPU visible" in err


def test_bf16_step(chip_smoke):
    assert chip_smoke.bf16_step(1.0) == 2.0 ** -7
    assert chip_smoke.bf16_step(5.0) == 2.0 ** -5
    assert chip_smoke.bf16_step(-0.3) == 2.0 ** -9


def test_greedy_check_passes_equal_and_fails_a_real_mismatch(chip_smoke):
    pair = cli_args.build_pair("llama3.2-1b", smoke=True)
    vocab = pair[4].vocab_size
    rng = np.random.default_rng(0)
    reqs = [ServeRequest(i, rng.integers(0, vocab, P).astype(np.int32), 6)
            for i, P in enumerate((5, 7, 5))]
    want = chip_smoke.reference_tokens(pair, reqs)
    for r in reqs:
        r.tokens = want[r.rid].copy()
    assert chip_smoke.greedy_failures("t", pair, reqs, want) == []
    # the last-ranked token at the first generated position is no near-tie
    r = reqs[1]
    logits = np.asarray(pair[0].apply(
        pair[2], r.tokens[None, :r.prompt_len])[0][0, -1])
    r.tokens[r.prompt_len] = int(np.argmin(logits))
    failures = chip_smoke.greedy_failures("t", pair, reqs, want)
    assert len(failures) == 1 and "request 1" in failures[0]
