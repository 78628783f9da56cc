"""CPU dry run of ``chip_smoke.py``'s control flow (the script itself has
no CPU mode): the same phase functions at a 2-layer, d=64 config —
train -> engine -> HTTP -> agreement oracle — plus the device gate."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
from deeplearning4j_tpu.common.environment import configure_compile_cache
from deeplearning4j_tpu.models.gpt import CausalLM
from deeplearning4j_tpu.models.transformer import tiny_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_main_stops_at_the_device_gate_on_cpu(monkeypatch, tmp_path,
                                              capsys):
    # with the variable set the cache setter leaves this process alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert exc.value.code not in (0, None)
    assert "platform='cpu'" in str(exc.value.code)
    assert capsys.readouterr().out == ""      # no result line


def test_last_line_has_exactly_ok_and_device():
    summary = {"ok": True, "claim": None, "phases": {"train": {}},
               "device": {"platform": "tpu", "kind": "TPU v5 lite",
                          "count": 1}}
    assert json.loads(chip_smoke.result_line(summary)) == {
        "ok": True, "device": {"platform": "tpu", "kind": "TPU v5 lite",
                               "count": 1}}


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    before = {n: getattr(jax.config, n) for n in names}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert configure_compile_cache() == str(tmp_path)
    assert {n: getattr(jax.config, n) for n in names} == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert configure_compile_cache() \
            == os.path.join(REPO, ".jax_cache") \
            == jax.config.jax_compilation_cache_dir
    finally:
        for n, v in before.items():
            jax.config.update(n, v)


def test_train_then_serve_over_http_at_toy_size():
    cfg = tiny_config(vocab=chip_smoke.PERIOD, max_len=32, d_model=64,
                      n_layers=2, n_heads=4, d_ff=128)
    cfg.dropout = 0.0
    model = CausalLM(cfg, compute_dtype=jnp.float32)
    params, train = chip_smoke.phase_train(
        model, batch=8, seq_len=24, steps=60, lr=3e-3, min_drop=2.0)
    assert train["ok"] and train["loss_last"] < train["loss_first"] - 2.0

    # outputs long enough to keep both requests in flight together
    # even on a model that decodes in a millisecond
    requests = ((9, 20), (4, 24))
    serve = chip_smoke.phase_serve(model, params, requests,
                                   min_agreement=0.9)
    assert serve["ok"] and serve["attn_mode"] == "xla"   # CPU default
    assert serve["warm_misses"] == 0
    assert serve["agreement_generate"] == 1.0            # f32: exact
    assert [r["tokens"] for r in serve["requests"]] == [20, 24]

    # the train phase states its margin and fails when it is not met
    with pytest.raises(AssertionError, match="stated margin"):
        chip_smoke.phase_train(model, batch=8, seq_len=24, steps=2,
                               lr=1e-6, min_drop=1.0)
