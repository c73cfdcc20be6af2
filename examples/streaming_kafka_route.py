"""End-to-end drive: broker route → streaming iterator → training.

A producer publishes NDArray records onto a topic (in-memory broker here;
swap ``default_client()`` for a real Kafka deployment), the pub/sub route
pumps them into the bounded-buffer streaming iterator, and plain
``MultiLayerNetwork.fit`` consumes them — the dl4j-streaming ingest shape,
TPU-native.
"""

import threading
import time

import numpy as np


def main():
    from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.data.kafka import (InMemoryBroker,
                                               NDArrayPublisher,
                                               NDArrayPubSubRoute)
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam

    broker = InMemoryBroker()
    route = NDArrayPubSubRoute(broker, "train-topic", batch_size=32).start()

    def producer():
        pub = NDArrayPublisher(broker, "train-topic")
        rs = np.random.RandomState(0)
        for _ in range(512):
            x = rs.randn(8).astype(np.float32)
            y = np.eye(2, dtype=np.float32)[int(x.sum() > 0)]
            pub.publish(x, y)
        # let the pump drain the topic, then end the stream so fit() stops
        while broker.pending("train-topic"):
            time.sleep(0.01)
        route.stop()

    t = threading.Thread(target=producer)
    t.start()

    conf = (NeuralNetConfiguration.builder()
            .seed(1).updater(Adam(1e-2)).weight_init("xavier")
            .list()
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(8))
            .build())
    net = MultiLayerNetwork(conf).init()
    net.fit(route.iterator)          # consumes until the stream ends
    t.join()
    print(f"trained from the stream: {net.iteration} iterations, "
          f"final score {net.get_score():.4f}")
    assert net.iteration > 0 and np.isfinite(net.get_score())
    print("STREAMING ROUTE PASS")


if __name__ == "__main__":
    main()
