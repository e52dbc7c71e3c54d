"""Examples smoke tests, second half (tiny shapes, CPU). The first half is
tests/test_examples.py: the driver's workers take a file each, and one file
of fifty child processes was the whole run's longest pole."""
import sys

from test_examples import EXAMPLES, _run


def test_ctc_ocr():
    r = _run("ctc/train_ctc_ocr.py", "--num-examples", "800",
             "--num-epochs", "25", timeout=1200)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "sequence accuracy" in r.stdout


def test_vae():
    r = _run("vae/train_vae.py", "--num-examples", "1000",
             "--num-epochs", "15")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "VAE TRAINING OK" in r.stdout


def test_bi_lstm_sort():
    r = _run("bi-lstm-sort/train_sort.py", "--num-examples", "2000",
             "--num-epochs", "20", timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "token accuracy" in r.stdout


def test_nce_loss():
    r = _run("nce-loss/train_nce.py", timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "rank-1 accuracy" in r.stdout


def test_neural_style():
    r = _run("neural-style/neural_style.py", "--size", "32", "--iters", "40")
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "NEURAL STYLE OK" in r.stdout


def test_fcn_segmentation():
    r = _run("fcn-xs/train_fcn.py", "--num-examples", "32",
             "--num-epochs", "10", timeout=600)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "FCN SEGMENTATION OK" in r.stdout


def test_speech_recognition_ctc():
    r = _run("speech_recognition/train_am.py", timeout=1500)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "SPEECH AM OK" in r.stdout


def test_parallel_actor_critic():
    r = _run("reinforcement-learning/parallel_actor_critic.py",
             "--updates", "400", timeout=900)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "PARALLEL ACTOR-CRITIC OK" in r.stdout


def test_stochastic_depth():
    r = _run("stochastic-depth/train_sd.py", "--num-epochs", "8",
             timeout=900)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "STOCHASTIC DEPTH OK" in r.stdout


def test_numpy_ops_custom_softmax():
    r = _run("numpy-ops/custom_softmax.py", "--num-epochs", "8")
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "CUSTOM NUMPY OP OK" in r.stdout


def test_profiler_example():
    r = _run("profiler/profiler_example.py")
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "PROFILER EXAMPLE OK" in r.stdout


def test_captcha_multihead():
    r = _run("captcha/train_captcha.py", "--num-epochs", "6", timeout=600)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "CAPTCHA OK" in r.stdout


def test_lstnet_forecast():
    r = _run("multivariate_time_series/train_lstnet.py", timeout=900)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "LSTNET FORECAST OK" in r.stdout


def test_sgld_posterior():
    r = _run("bayesian-methods/sgld_regression.py", timeout=900)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "SGLD OK" in r.stdout


def test_dsd_training():
    r = _run("dsd/train_dsd.py", timeout=900)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "DSD OK" in r.stdout


def test_rnn_time_major():
    r = _run("rnn-time-major/readme_bench.py", "--steps", "10", timeout=900)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "RNN TIME-MAJOR OK" in r.stdout


def test_module_walkthrough():
    r = _run("module/mod_walkthrough.py", timeout=600)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "MODULE WALKTHROUGH OK" in r.stdout


def test_python_howto():
    r = _run("python-howto/data_and_ops.py", timeout=600)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "PYTHON HOWTO OK" in r.stdout


def test_memcost_remat():
    r = _run("memcost/memonger_demo.py", timeout=600)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "MEMCOST REMAT OK" in r.stdout


def test_onnx_roundtrip_example():
    r = _run("onnx/roundtrip.py", timeout=600)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "ONNX EXAMPLE OK" in r.stdout


def test_capsnet_routing():
    r = _run("capsnet/train_capsnet.py", "--num-epochs", "6", timeout=1200)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "CAPSNET OK" in r.stdout


def test_deep_embedded_clustering():
    r = _run("deep-embedded-clustering/dec.py", timeout=900)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "DEC OK" in r.stdout


def test_sparse_embedding_end2end():
    # shrunk table: below the 500k gate for the wall-clock assert, which
    # is machine-load sensitive (the O(nnz) guarantee is asserted
    # deterministically in tests/test_sparse.py)
    r = _run("sparse/sparse_embedding/train.py", "--rows", "100000",
             "--steps", "80", timeout=900)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "SPARSE EMBEDDING OK" in r.stdout


def test_kaggle_pipeline():
    r = _run("kaggle-ndsb1/train_predict_submit.py", "--num-train", "300",
             "--num-epochs", "6", timeout=900)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "KAGGLE PIPELINE OK" in r.stdout


def test_chinese_text_cnn():
    r = _run("cnn_chinese_text_classification/text_cnn_zh.py",
             "--num-examples", "800", "--num-epochs", "4", timeout=900)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "final chinese text-cnn accuracy" in r.stdout
    acc = float(r.stdout.rsplit("accuracy:", 1)[1])
    assert acc > 0.8, acc


def test_kaggle_ndsb2():
    r = _run("kaggle-ndsb2/train_ndsb2.py", "--num-examples", "200",
             "--num-epochs", "6", timeout=900)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "final NDSB2 val CRPS" in r.stdout


def test_adversarial_vae():
    r = _run("mxnet_adversarial_vae/vaegan.py", "--num-examples", "512",
             "--num-epochs", "6", "--batch-size", "32", timeout=900)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "final VAE-GAN pixel recon MSE" in r.stdout


def test_utils_get_data():
    sys.path.insert(0, EXAMPLES)
    try:
        from utils import get_mnist_iterator, get_cifar10_iterator
        train, val = get_mnist_iterator(25, num_train=100, num_val=50)
        b = next(iter(train))
        assert b.data[0].shape == (25, 1, 28, 28)
        ctrain, _ = get_cifar10_iterator(20, num_train=60, num_val=20)
        cb = next(iter(ctrain))
        assert cb.data[0].shape == (20, 3, 32, 32)
    finally:
        sys.path.remove(EXAMPLES)
