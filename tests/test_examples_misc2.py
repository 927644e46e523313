"""CI tests for the second batch of example families: autoencoder/DEC,
text CNN, NCE, stochastic depth, module-API demos, SGLD, FCN
segmentation, neural style, DQN.

Each asserts the example's headline behavior at tiny scale, reference
`tests/python/train` style.
"""
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for sub in ("autoencoder", "dec", "cnn_text_classification", "nce_loss",
            "stochastic_depth", "module_api", "bayesian_methods",
            "fcn_xs", "neural_style", "reinforcement_learning"):
    sys.path.insert(0, os.path.join(ROOT, "examples", sub))


def test_stacked_autoencoder_reconstructs():
    import mnist_sae
    mse, var, _ = mnist_sae.train(dims=(64, 16), n=1500, pre_epochs=2,
                                  fine_epochs=10)
    assert mse < 0.3 * var, (mse, var)


def test_dec_improves_or_holds_clustering():
    import mxnet_tpu as mx
    import dec
    # initializers draw from the global RNGs: pin them so the SAE
    # embedding (and thus the k-means seed clustering) is reproducible
    np.random.seed(0)
    mx.random.seed(0)
    acc0, acc = dec.train(clusters=4, n=1200, epochs=10)
    # blobs are separable: DEC should hold near-perfect clustering
    assert acc > 0.9, (acc0, acc)


def test_text_cnn_learns_trigram_signal():
    import text_cnn
    acc = text_cnn.train(epochs=4, batch_size=100)
    assert acc > 0.85, acc


def test_toy_nce_auc():
    import toy_nce
    auc = toy_nce.train(epochs=6)
    assert auc > 0.85, auc


def test_stochastic_depth_trains():
    """In a process of its own.  The pinned trajectory is chaotic: the
    stochastic gates and momentum amplify reduction-order rounding, so at
    10 epochs the SAME seed lands anywhere in 0.65-0.83 depending on the
    XLA host-device partition, and inside one process that had run the
    whole suite before it (whatever partition and rounding state 800
    tests leave behind; bisected 2026-08 to no smaller set) it landed
    below the bar where alone, at file scope, and after any subset it
    passed.  By 20 epochs training has converged through that transition
    on every measured partition (>= 0.94).  A new process under the
    suite's own environment (``conftest.py``'s eight CPU devices) takes
    the rest of the suite out of the question."""
    import subprocess
    code = ("import sys; sys.path.insert(0, %r); sys.path.insert(0, %r)\n"
            "import numpy as np, mxnet_tpu as mx, sd_mnist\n"
            "mx.random.seed(42); np.random.seed(42)\n"
            "print('acc=%%.4f' %% sd_mnist.train("
            "epochs=20, batch_size=100, num_blocks=2))"
            % (ROOT, os.path.join(ROOT, "examples", "stochastic_depth")))
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, cwd=ROOT)
    assert res.returncode == 0, res.stdout + res.stderr
    acc = float(res.stdout.strip().rsplit("acc=", 1)[1])
    assert acc > 0.75, acc


def test_module_api_walkthrough():
    import mnist_mlp
    acc = mnist_mlp.train(epochs=3)
    assert acc > 0.9, acc


def test_sequential_module_chain():
    import sequential_module
    acc = sequential_module.train(epochs=3)
    assert acc > 0.9, acc


def test_python_loss_module_hinge():
    import python_loss
    acc = python_loss.train(epochs=4)
    assert acc > 0.9, acc


def test_sgld_posterior_mean_beats_last_sample():
    import sgld_demo
    last_rmse, post_rmse = sgld_demo.train(total_epochs=30, burn_in=15)
    assert post_rmse < 0.2, (last_rmse, post_rmse)
    assert post_rmse <= last_rmse * 1.05, (last_rmse, post_rmse)


def test_fcn_segmentation_beats_background():
    import fcn_xs
    acc, bg = fcn_xs.train(epochs=10, batch_size=16)
    assert acc > bg + 0.1, (acc, bg)


def test_neural_style_loss_decreases():
    import nstyle
    history = nstyle.run(iters=40, size=32)
    assert history[-1] < 0.5 * history[0], (history[0], history[-1])


def test_dqn_cartpole_improves():
    import dqn_cartpole
    lengths = dqn_cartpole.train(episodes=200, eps_decay_episodes=100)
    first = np.mean(lengths[:10])
    best20 = max(np.mean(lengths[i:i + 20])
                 for i in range(0, len(lengths) - 19))
    # random policy balances ~10-25 steps; a working DQN reaches the
    # 200-step cap (measured ~195 at episode 200)
    assert best20 > 80, (first, best20)
    assert best20 > first + 40, (first, best20)


def test_time_major_lstm_beats_unigram():
    sys.path.insert(0, os.path.join(ROOT, "examples", "rnn_time_major"))
    import lstm_time_major
    ppl = lstm_time_major.train(epochs=3)
    # uniform/unigram perplexity over the dirichlet(0.1) corpus is far
    # higher; the Markov structure should pull it well under vocab/2
    assert ppl < 30, ppl


def test_captcha_multi_digit():
    sys.path.insert(0, os.path.join(ROOT, "examples", "captcha"))
    import train_captcha
    per_digit, exact = train_captcha.train(epochs=5)
    assert per_digit > 0.9, per_digit
    assert exact > 0.7, exact
