"""The evaluation-ladder examples (ResNet-18, Transformer-LM) end-to-end
on the 8-device virtual mesh — BASELINE.json rungs 3 and 4. Small shapes;
asserts finite, recorded losses and the data-plumbing contracts."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples"))

import distributed_pytorch_tpu as dist  # noqa: E402
import train_resnet  # noqa: E402
import train_transformer_lm  # noqa: E402


def test_transformer_lm_dp():
    h = []
    dist.launch(train_transformer_lm.main_worker,
                ["--steps", "4", "--batch-size", "1", "--seq-len", "16",
                 "--dim", "16", "--n-layers", "1", "--n-heads", "2",
                 "--data-size", "64"], True, h)
    assert len(h) == 4
    assert all(np.isfinite(x) for x in h)


def test_transformer_lm_fsdp_flash():
    h = []
    dist.launch(train_transformer_lm.main_worker,
                ["--steps", "4", "--batch-size", "1", "--seq-len", "16",
                 "--dim", "16", "--n-layers", "1", "--n-heads", "2",
                 "--data-size", "64", "--fsdp", "--flash"], True, h)
    assert len(h) == 4 and all(np.isfinite(x) for x in h)


def test_transformer_lm_byte_corpus(tmp_path):
    text = tmp_path / "corpus.txt"
    text.write_bytes(bytes(range(64)) * 40)
    corpus = train_transformer_lm.ByteCorpus(str(text), seq_len=16)
    x, y = corpus[0]
    assert x.shape == (16,) and y.shape == (16,)
    np.testing.assert_array_equal(y[:-1], x[1:])  # shifted-by-one targets
    h = []
    dist.launch(train_transformer_lm.main_worker,
                ["--steps", "3", "--batch-size", "1", "--seq-len", "16",
                 "--dim", "16", "--n-layers", "1", "--n-heads", "2",
                 "--text", str(text)], True, h)
    assert len(h) == 3 and all(np.isfinite(x) for x in h)


@pytest.mark.slow
def test_resnet_synthetic():
    h = []
    dist.launch(train_resnet.main_worker,
                ["--epochs", "2", "--batch-size", "2", "--data-size", "64",
                 "--limit-steps", "2"], True, h)
    assert len(h) == 4  # 2 epochs x 2 capped steps
    assert all(np.isfinite(x) for x in h)


def test_resnet_eval():
    h = []
    dist.launch(train_resnet.main_worker,
                ["--epochs", "1", "--batch-size", "2", "--data-size", "128",
                 "--limit-steps", "1", "--eval"], True, h)
    assert h and all(np.isfinite(x) for x in h)


def test_transformer_lm_eval_and_generate():
    h = []
    dist.launch(train_transformer_lm.main_worker,
                ["--steps", "3", "--batch-size", "1", "--seq-len", "16",
                 "--dim", "16", "--n-layers", "1", "--n-heads", "2",
                 "--data-size", "128", "--eval", "--generate", "4"], True, h)
    assert len(h) == 3 and all(np.isfinite(x) for x in h)


def test_resnet_missing_cifar_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        train_resnet.Cifar10(str(tmp_path))


def test_cifar10_reader(tmp_path):
    """The pickle-batch reader against a synthetic CIFAR-layout dir."""
    import pickle
    d = tmp_path / "cifar-10-batches-py"
    d.mkdir()
    rng = np.random.default_rng(0)
    for i in range(1, 6):
        data = rng.integers(0, 256, (20, 3072), dtype=np.uint8)
        with open(d / f"data_batch_{i}", "wb") as f:
            pickle.dump({b"data": data,
                         b"labels": list(rng.integers(0, 10, 20))}, f)
    ds = train_resnet.Cifar10(str(tmp_path))
    assert len(ds) == 100
    x, y = ds[0]
    assert x.shape == (32, 32, 3) and x.dtype == np.float32
    assert 0 <= int(y) < 10


def test_transformer_lm_checkpoint_resume_exact(tmp_path):
    """Interrupted-and-resumed training equals the uninterrupted run
    exactly: run A trains 9 steps straight; run B trains 5 steps saving at
    step 4, then a FRESH process state resumes from the checkpoint and
    finishes to 9. Loss histories for the continued steps must match
    bit-for-bit (same params, same opt state, same fast-forwarded data
    stream)."""
    ckpt = str(tmp_path / "ck")
    common = ["--batch-size", "2", "--seq-len", "16", "--dim", "16",
              "--n-layers", "1", "--n-heads", "2", "--data-size", "16",
              "--log-every", "1"]

    full = []
    dist.launch(train_transformer_lm.main_worker,
                ["--steps", "9"] + common, True, full)

    part = []
    dist.launch(train_transformer_lm.main_worker,
                ["--steps", "5", "--save", ckpt, "--save-every", "4"]
                + common, True, part)
    resumed = []
    dist.launch(train_transformer_lm.main_worker,
                ["--steps", "9", "--save", ckpt, "--resume",
                 "--save-every", "100"] + common, True, resumed)

    from distributed_pytorch_tpu.utils.checkpoint import latest_step
    # run B saved at 4 (interval) and force-saved at its last step
    assert latest_step(ckpt) == 8
    # resumed run continued at step 5..8 (4 steps)
    assert len(resumed) == 4
    np.testing.assert_array_equal(np.asarray(resumed),
                                  np.asarray(full[5:9]))


@pytest.mark.slow
@pytest.mark.parametrize("extra", [[], ["--sp-core", "striped"],
                                   ["--sp-core", "ulysses"],
                                   ["--window", "48"]])
def test_long_context_sp_modes(extra):
    """Sequence-parallel long-context training in every attention mode:
    contiguous ring-flash, striped (data-level token striping), ulysses
    (all-to-all), and sliding-window ring; loss finite over a few
    steps."""
    import train_long_context

    h = []
    train_long_context.main(
        ["--steps", "6", "--seq-len", "128", "--sp", "4",
         "--batch-size", "2", "--dim", "32", "--n-layers", "1",
         "--n-heads", "4", "--block-q", "16", "--block-k", "16"] + extra,
        quiet=True, history=h)
    assert len(h) == 5
    assert all(np.isfinite(x) for x in h)


@pytest.mark.slow
def test_transformer_lm_prefetch():
    """--prefetch N: batches arrive on device from the background thread;
    losses match the unprefetched run exactly (same data order)."""
    h0, h1 = [], []
    args = ["--steps", "6", "--batch-size", "1", "--seq-len", "16",
            "--dim", "16", "--n-layers", "1", "--n-heads", "2",
            "--data-size", "64", "--log-every", "1"]
    dist.launch(train_transformer_lm.main_worker, args, True, h0)
    dist.launch(train_transformer_lm.main_worker,
                args + ["--prefetch", "2"], True, h1)
    np.testing.assert_array_equal(np.asarray(h0), np.asarray(h1))


@pytest.mark.slow
@pytest.mark.parametrize("router", ["tokens", "experts"])
def test_moe_lm_example(router):
    """Expert-parallel MoE rung: dp x ep mesh, both routers; loss finite
    and decreasing over a few steps."""
    import train_moe_lm

    h = []
    train_moe_lm.main(
        ["--steps", "6", "--seq-len", "32", "--batch-size", "4",
         "--ep", "4", "--n-experts", "4", "--dim", "32", "--n-layers", "1",
         "--n-heads", "4", "--router", router],
        quiet=True, history=h)
    assert len(h) == 5
    assert all(np.isfinite(x) for x in h)
    assert h[-1] < h[0]
