"""End-to-end drive: LeNet on MNIST through the public API.

Builds the BASELINE config #1 network, trains 2 epochs on the bundled
(synthetic-fallback) MNIST, asserts accuracy, round-trips a checkpoint, and
exercises the stateful RNN inference path on a small LSTM.
"""

import os
import sys
import tempfile

import numpy as np


def main():
    import jax
    print("devices:", jax.devices())

    from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import (ConvolutionLayer,
                                              SubsamplingLayer, DenseLayer,
                                              OutputLayer)
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.data.fetchers import MnistDataSetIterator

    conf = (NeuralNetConfiguration.builder()
            .seed(12345)
            .updater(Adam(1e-3))
            .weight_init("xavier")
            .list()
            .layer(ConvolutionLayer(n_out=20, kernel_size=5, stride=1,
                                    activation="relu"))
            .layer(SubsamplingLayer(pooling_type="max", kernel_size=2, stride=2))
            .layer(ConvolutionLayer(n_out=50, kernel_size=5, stride=1,
                                    activation="relu"))
            .layer(SubsamplingLayer(pooling_type="max", kernel_size=2, stride=2))
            .layer(DenseLayer(n_out=500, activation="relu"))
            .layer(OutputLayer(n_out=10, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.convolutional(28, 28, 1))
            .build())
    net = MultiLayerNetwork(conf).init()

    # the synthetic-fallback MNIST is deliberately non-trivial (~98% Bayes
    # ceiling: overlapping smooth class templates + 1% label noise), so a
    # few epochs land mid-90s rather than a meaningless 100
    train = MnistDataSetIterator(128, train=True, num_examples=6400,
                                 flatten=False)
    test = MnistDataSetIterator(256, train=False, num_examples=1024,
                                flatten=False)
    net.fit(train, epochs=6)
    ev = net.evaluate(test)
    acc = ev.accuracy()
    print(f"accuracy after 6 epochs: {acc:.4f}")
    assert acc > 0.85, f"accuracy {acc} too low"

    # checkpoint round-trip
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "lenet.zip")
        net.save(p)
        net2 = MultiLayerNetwork.load(p)
        x = np.random.RandomState(0).rand(4, 28, 28, 1).astype(np.float32)
        o1, o2 = np.asarray(net.output(x)), np.asarray(net2.output(x))
        assert np.allclose(o1, o2, atol=1e-6), "save/load output mismatch"
    print("checkpoint round-trip: OK")

    # error-path probes
    try:
        (NeuralNetConfiguration.builder().list()
         .layer(DenseLayer(n_out=4, activation="not_an_act"))
         .layer(OutputLayer(n_out=2, loss="mcxent"))
         .set_input_type(InputType.feed_forward(3)).build())
        MultiLayerNetwork(_ := None)
    except Exception as e:
        print(f"bad activation raised: {type(e).__name__}: {e}")

    try:
        conf_bad = (NeuralNetConfiguration.builder().list()
                    .layer(DenseLayer(n_out=4))
                    .layer(OutputLayer(n_out=2, loss="mcxent"))
                    .build())
        MultiLayerNetwork(conf_bad).init()
        raise AssertionError("expected error for missing n_in/input type")
    except AssertionError:
        raise
    except Exception as e:
        print(f"missing input type raised: {type(e).__name__}")

    # stateful rnn inference
    from deeplearning4j_tpu.nn.layers import LSTM, RnnOutputLayer
    rconf = (NeuralNetConfiguration.builder()
             .seed(1).updater(Adam(1e-3)).list()
             .layer(LSTM(n_out=8, activation="tanh"))
             .layer(RnnOutputLayer(n_out=3, activation="softmax", loss="mcxent"))
             .set_input_type(InputType.recurrent(5)).build())
    rnet = MultiLayerNetwork(rconf).init()
    xt = np.random.RandomState(1).rand(2, 1, 5).astype(np.float32)
    o1 = np.asarray(rnet.rnn_time_step(xt))
    o2 = np.asarray(rnet.rnn_time_step(xt))
    assert not np.allclose(o1, o2), "rnn_time_step not stateful"
    rnet.rnn_clear_previous_state()
    o3 = np.asarray(rnet.rnn_time_step(xt))
    assert np.allclose(o1, o3, atol=1e-6), "state clear broken"
    print("rnn_time_step statefulness: OK")
    print("VERIFY PASS")


if __name__ == "__main__":
    main()
