"""chip_smoke.py rehearsed on the CPU at a tiny size.

The script itself refuses to run without a TPU and has no option that
lets it; the test-only path is here, inside the test: sizes are cut by
patching the script's module constants, the platform check is bypassed
by calling the phases directly, and the serving phase is steered to the
Pallas kernels in interpret mode (on the chip 'auto' picks them).  What
this pins is the script's control flow, arguments and correctness checks
- never a time.
"""
import functools
import json
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke as cs

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setitem(cs.BERT, "batch_size", 8)
    for k, v in dict(seq_len=16, vocab_size=100, hidden=32, n_layers=2,
                     n_heads=2, ffn_size=64, max_preds_per_seq=4).items():
        monkeypatch.setitem(cs.BERT, k, v)
    for k, v in dict(batch_size=8, img_shape=(3, 32, 32),
                     class_num=10).items():
        monkeypatch.setitem(cs.RESNET, k, v)
    for k, v in dict(vocab_size=97, d_model=32, num_layers=2, num_heads=2,
                     ffn_dim=64, max_seq_len=128).items():
        monkeypatch.setitem(cs.GPT2, k, v)
    monkeypatch.setattr(cs, "PROMPT_LENS", (40, 5, 20))
    monkeypatch.setattr(cs, "NEW_TOKENS", 4)
    monkeypatch.setattr(cs, "DP_BERT_BATCH", 16)
    monkeypatch.setattr(cs, "peak_bytes", lambda dev: None)


def test_refuses_to_run_without_a_tpu():
    """As the driver runs it in the sandbox: non-zero, no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=_ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout and "needs a TPU" in proc.stderr


def test_bert_trainer_phase(tiny, capsys):
    b = cs.BERT["batch_size"]
    losses = cs.train_phase("bert_base_train", cs.bert_program(b),
                            cs.bert_feed(b), b)
    assert len(losses) == cs.TRAIN_CALLS * cs.TRAIN_STEPS
    assert '"phase": "bert_base_train"' in capsys.readouterr().out


def test_resnet_trainer_phase(tiny):
    losses = cs.train_phase("resnet50_train", cs.resnet_program(),
                            cs.resnet_feed(), cs.RESNET["batch_size"])
    assert losses[-1] < losses[0]


def test_server_phase_through_the_kernels(tiny, monkeypatch, capsys):
    import paddle_tpu.serving as serving

    monkeypatch.setattr(
        serving, "DecodeConfig",
        functools.partial(serving.DecodeConfig, use_pallas="always",
                          interpret=True))
    # interpret mode lowers the kernel to plain HLO: the chip-only
    # marker check is the one assertion that cannot hold here
    monkeypatch.setattr(cs, "assert_kernel_in_step", lambda eng, label: None)
    cs.server_phase()
    out = capsys.readouterr().out
    assert '"phase": "server_decode_kernel"' in out
    assert '"phase": "server_chunk_kernel"' in out
    chunk_line = out.split("server_chunk_kernel")[1].split("\n")[0]
    assert '"prefill_chunks": 0' not in chunk_line
    # the served-precision round: kernel step against the engine's own
    # reference step, logits recorded, precision put back as it was
    lines = [json.loads(ln) for ln in out.splitlines()]
    by_phase = {ln["phase"]: ln for ln in lines}
    assert by_phase["server_decode_kernel"]["matmul_precision"] == "highest"
    assert by_phase["server_served_precision"]["matmul_precision"] == \
        "default"
    assert not by_phase["server_served_precision_witness"][
        "pallas_in_decode_step"]
    agreement = by_phase["server_served_precision_agreement"]
    assert agreement["steps_compared"] == agreement["tokens_agree"] == \
        len(cs.PROMPT_LENS) * cs.NEW_TOKENS
    assert agreement["worst_logit_rel_err"] < 1e-4  # f32 on the CPU
    assert jax.config.jax_default_matmul_precision is None


def test_served_precision_round_refuses_a_wrong_step(tiny, monkeypatch):
    """The logit bar is what guards the served-precision round: a kernel
    step whose logits are off by more than rounding must fail it."""
    import numpy as np

    real = cs.serve

    def skewed(label, *a, **kw):
        toks, logits = real(label, *a, **kw)
        if kw.get("kernel", True):
            logits = [lg * 1.2 for lg in logits]
        return toks, logits

    monkeypatch.setattr(cs, "serve", skewed)
    monkeypatch.setattr(cs, "assert_kernel_in_step", lambda eng, label: None)
    from paddle_tpu.serving.decode import TransformerLM

    model = TransformerLM(**cs.GPT2)
    weights = model.init_weights(jax.random.PRNGKey(0))
    prompts = [np.arange(1, n + 1).tolist() for n in cs.PROMPT_LENS]
    with pytest.raises(AssertionError, match="logits differ"):
        cs.served_precision_round(model, weights, prompts)


def test_kernel_marker_check_fails_on_a_reference_step(tiny):
    """On the CPU 'auto' resolves to the reference: the check that
    guards the chip run must refuse exactly that."""
    from paddle_tpu.serving import DecodeConfig, DecodeEngine
    from paddle_tpu.serving.decode import TransformerLM

    model = TransformerLM(**cs.GPT2)
    eng = DecodeEngine(model, model.init_weights(jax.random.PRNGKey(0)),
                       DecodeConfig(max_seq_len=128))
    with pytest.raises(AssertionError, match="no Pallas kernel"):
        cs.assert_kernel_in_step(eng, "cpu")


def test_four_chip_phase_on_virtual_devices(tiny, tmp_path, monkeypatch,
                                            capsys):
    monkeypatch.setattr(cs, "HLO_DIR", str(tmp_path / "hlo"))
    cs.dp_phase(jax.devices())
    out = capsys.readouterr().out
    assert '"phase": "bert_fleet_dp"' in out
    assert f'"param_devices": {len(jax.devices())}' in out
