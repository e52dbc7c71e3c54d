"""Examples smoke tests (tiny shapes, CPU) — each BASELINE.json config's
script must run end-to-end and learn on its synthetic data."""
import os
import subprocess
import sys

import pytest

EXAMPLES = os.path.join(os.path.dirname(__file__), "..", "examples")


def _run(rel, *args, timeout=420):
    env = dict(os.environ,
               PYTHONPATH=os.path.join(EXAMPLES, ".."))
    return subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, rel)] + list(args),
        capture_output=True, text=True, env=env, timeout=timeout)


def test_train_mnist_mlp():
    r = _run("image-classification/train_mnist.py", "--num-epochs", "4",
             "--num-examples", "600", "--batch-size", "50")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "final validation" in r.stdout


def test_word_lm():
    r = _run("rnn/word_lm/train.py", "--num-epochs", "1",
             "--max-sentences", "300", "--batch-size", "25",
             "--num-hidden", "32", "--num-embed", "16",
             "--data", "/nonexistent")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "final train perplexity" in r.stdout


def test_ssd():
    r = _run("ssd/train_ssd.py", "--num-batches", "30", "--batch-size", "8")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "detections kept after NMS" in r.stdout


def test_factorization_machine():
    r = _run("sparse/factorization_machine/train.py", "--num-epochs", "15",
             "--num-examples", "2400", "--num-features", "200",
             "--lr", "0.01")
    assert r.returncode == 0, r.stderr[-2000:]
    acc = float(r.stdout.strip().split()[-1])
    assert acc > 0.6, r.stdout


def test_wide_deep():
    r = _run("sparse/wide_deep/train.py", "--num-epochs", "6",
             "--num-examples", "1200", "--num-sparse", "400")
    assert r.returncode == 0, r.stderr[-2000:]
    acc = float(r.stdout.strip().split()[-1])
    assert acc > 0.7, r.stdout


def test_model_parallel_lstm():
    r = _run("model-parallel/lstm_sharded.py", "--steps", "3",
             "--seq-len", "8", "--batch-size", "2", "--num-hidden", "32")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "sharded LSTM train OK" in r.stdout


def test_model_parallel_lstm_group2ctx():
    """Reference example/model-parallel/lstm pattern: per-layer ctx_group
    + Module(group2ctxs=...) on distinct virtual devices."""
    r = _run("model-parallel/lstm_group2ctx.py", "--num-epoch", "2",
             "--samples", "128", "--seq-len", "6", "--num-hidden", "24")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "next-token accuracy" in r.stdout
    assert "TFRT_CPU_1" in r.stdout  # layer 1 really lives elsewhere


def test_gluon_resnet_tiny():
    r = _run("gluon/train_resnet50.py", "--model", "resnet18_v1",
             "--batch-size", "2", "--image-size", "32",
             "--num-classes", "10", "--num-batches", "2", "--ctx", "cpu")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "img/s" in r.stdout


def test_pipeline_mlp():
    r = _run("model-parallel/pipeline_mlp.py", "--steps", "10",
             "--micro-batches", "4", "--micro-size", "2", "--hidden", "8")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "pipeline training OK" in r.stdout


def test_moe_example():
    r = _run("moe/train_moe.py", "--steps", "10", "--tokens", "32",
             "--dim", "8")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "MoE training OK" in r.stdout


def test_faster_rcnn():
    r = _run("rcnn/train_faster_rcnn.py", "--num-steps", "20")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "FASTER-RCNN FLOW OK" in r.stdout


def test_deformable_rcnn():
    r = _run("rcnn/train_faster_rcnn.py", "--num-steps", "15",
             "--deformable")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "FASTER-RCNN FLOW OK" in r.stdout


def test_faster_rcnn_ohem():
    """Hardest-first ROI sampling (round 5; the reference LOG(FATAL)s
    on ohem=True — proposal_target-inl.h:133)."""
    r = _run("rcnn/train_faster_rcnn.py", "--num-steps", "15", "--ohem")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "FASTER-RCNN FLOW OK" in r.stdout


def test_faster_rcnn_ohem_deformable():
    """OHEM scoring must ride the SAME pooling path the deformable head
    trains on (a separate ROIPooling scoring pass pinned the deferred
    Dense to the wrong width — review-caught crash)."""
    r = _run("rcnn/train_faster_rcnn.py", "--num-steps", "10", "--ohem",
             "--deformable")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "FASTER-RCNN FLOW OK" in r.stdout


def test_adversary_fgsm():
    r = _run("adversary/fgsm_mnist.py", "--num-examples", "600",
             "--num-epochs", "3")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "adversarial accuracy" in r.stdout


def test_autoencoder():
    r = _run("autoencoder/train_autoencoder.py", "--num-examples", "600",
             "--num-epochs", "12")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "final reconstruction loss" in r.stdout


def test_gan():
    r = _run("gan/train_gan.py", "--num-iters", "250")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "sample mean" in r.stdout


def test_multitask():
    r = _run("multi-task/train_multitask.py", "--num-examples", "800",
             "--num-epochs", "5")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "parity accuracy" in r.stdout


def test_svm_mnist():
    r = _run("svm_mnist/train_svm.py", "--num-examples", "800",
             "--num-epochs", "6")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "final svm accuracy" in r.stdout


def test_long_context_ring_lm():
    r = _run("long-context/train_long_lm.py", "--seq-len", "256",
             "--steps", "20", "--dim", "32", "--layers", "1")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "LONG-CONTEXT TRAINING OK" in r.stdout


def test_cnn_text_classification():
    r = _run("cnn_text_classification/train_cnn_text.py",
             "--num-examples", "1000", "--num-epochs", "4")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "final text-cnn accuracy" in r.stdout


def test_recommender_mf():
    r = _run("recommenders/train_mf.py")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "final test mse" in r.stdout


def test_quantization_example():
    r = _run("quantization/quantize_mlp.py", "--num-examples", "1200",
             "--num-epochs", "5")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "int8 accuracy" in r.stdout
