"""Cross-process broker streaming (deeplearning4j_tpu/streaming/ — the
dl4j-streaming Kafka/Camel analog: CamelKafkaRouteBuilder.java:16,
kafka/NDArrayPublisher.java, kafka/NDArrayConsumer.java).

The headline test is the reference's end-to-end contract: a producer in a
SEPARATE PROCESS publishes minibatches to a broker topic while this
process trains ``net.fit`` on the subscribed route."""

import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.streaming import (
    NDArrayConsumer,
    NDArrayPublisher,
    NDArrayRoute,
    StreamingBroker,
    StreamStalled,
    dataset_from_bytes,
    dataset_to_bytes,
)


def _net():
    from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers.core import (DenseLayer,
                                                        OutputLayer)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updater import Adam

    conf = (NeuralNetConfiguration.builder().seed(7)
            .updater(Adam(learning_rate=1e-2))
            .list(DenseLayer(n_out=8, activation="relu"),
                  OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(4)).build())
    return MultiLayerNetwork(conf).init()


class TestSerde:
    def test_roundtrip_with_masks(self):
        rs = np.random.RandomState(0)
        ds = DataSet(rs.randn(3, 5, 7).astype(np.float32),
                     rs.randn(3, 5, 2).astype(np.float32),
                     features_mask=(rs.rand(3, 7) > 0.3).astype(np.float32),
                     labels_mask=(rs.rand(3, 7) > 0.3).astype(np.float32))
        back = dataset_from_bytes(dataset_to_bytes(ds))
        np.testing.assert_array_equal(back.features, ds.features)
        np.testing.assert_array_equal(back.labels, ds.labels)
        np.testing.assert_array_equal(back.features_mask, ds.features_mask)
        np.testing.assert_array_equal(back.labels_mask, ds.labels_mask)

    def test_roundtrip_without_masks(self):
        ds = DataSet(np.ones((2, 3), np.float32), np.eye(2, dtype=np.float32))
        back = dataset_from_bytes(dataset_to_bytes(ds))
        np.testing.assert_array_equal(back.features, ds.features)
        assert back.features_mask is None and back.labels_mask is None


class TestBrokerInProcess:
    def test_pub_sub_roundtrip(self):
        broker = StreamingBroker(port=0).start()
        try:
            with NDArrayConsumer("127.0.0.1", broker.port, "t1") as cons, \
                    NDArrayPublisher("127.0.0.1", broker.port, "t1") as pub:
                sent = [DataSet(np.full((2, 3), i, np.float32),
                                np.eye(2, dtype=np.float32))
                        for i in range(5)]
                for ds in sent:
                    pub.publish(ds)
                pub.end()
                got = list(cons)
            assert len(got) == 5
            for i, ds in enumerate(got):
                assert float(ds.features[0, 0]) == i
        finally:
            broker.stop()

    def test_ack_means_registered(self, monkeypatch):
        """The subscription ack must not leave the broker before the
        subscriber is in the topic's fan-out list: a consumer that has its
        ack publishes at once, and frames fanned out to a list it is not in
        yet are lost with the END frame — the consumer then waits forever
        (seen as a hang of test_pub_sub_roundtrip on a loaded machine). The
        writer thread is started before registration; stall the step
        between the two and the ack must wait with it."""
        broker = StreamingBroker(port=0).start()
        track = broker._track

        def slow_track(t):
            if "broker-writer" in t.name:
                time.sleep(0.3)  # between the writer's start and _subs
            track(t)

        monkeypatch.setattr(broker, "_track", slow_track)
        try:
            with NDArrayConsumer("127.0.0.1", broker.port, "t1",
                                 idle_timeout_s=5.0) as cons, \
                    NDArrayPublisher("127.0.0.1", broker.port, "t1") as pub:
                for i in range(3):
                    pub.publish_arrays(np.full((1, 2), i, np.float32),
                                       np.zeros((1, 2), np.float32))
                pub.end()
                got = list(cons)
            assert [float(ds.features[0, 0]) for ds in got] == [0, 1, 2]
        finally:
            broker.stop()

    def test_fan_out_two_subscribers(self):
        """Every subscriber sees every frame (Kafka
        consumer-group-per-subscriber semantics)."""
        import threading

        broker = StreamingBroker(port=0).start()
        try:
            c1 = NDArrayConsumer("127.0.0.1", broker.port, "t2")
            c2 = NDArrayConsumer("127.0.0.1", broker.port, "t2")
            out1, out2 = [], []
            t1 = threading.Thread(target=lambda: out1.extend(c1))
            t2 = threading.Thread(target=lambda: out2.extend(c2))
            t1.start()
            t2.start()
            with NDArrayPublisher("127.0.0.1", broker.port, "t2") as pub:
                for i in range(4):
                    pub.publish_arrays(np.full((1, 2), i, np.float32),
                                       np.ones((1, 1), np.float32))
                pub.end()
            t1.join(10)
            t2.join(10)
            assert len(out1) == 4 and len(out2) == 4
        finally:
            broker.stop()

    def test_thread_registry_bounded_over_reconnect_cycles(self):
        """A long-lived broker serving many connect/disconnect cycles must
        not accumulate one dead Thread object per connection: the registry
        prunes finished threads, keeping O(live) entries after 50 cycles."""
        broker = StreamingBroker(port=0).start()
        try:
            for i in range(50):
                with NDArrayPublisher("127.0.0.1", broker.port,
                                      "tb") as pub:
                    pub.publish_arrays(np.full((1, 2), i, np.float32),
                                       np.ones((1, 1), np.float32))
            # pruning happens as threads are tracked, so the registry
            # holds the accept thread plus at most the last few
            # connections still winding down — never all 50
            assert len(broker._threads) < 10, len(broker._threads)
            assert any(t.name == "broker-accept" and t.is_alive()
                       for t in broker._threads)
        finally:
            broker.stop()

    def test_topics_are_isolated(self):
        broker = StreamingBroker(port=0).start()
        try:
            ca = NDArrayConsumer("127.0.0.1", broker.port, "a")
            with NDArrayPublisher("127.0.0.1", broker.port, "a") as pa, \
                    NDArrayPublisher("127.0.0.1", broker.port, "b") as pb:
                pb.publish_arrays(np.zeros((1, 1), np.float32),
                                  np.zeros((1, 1), np.float32))
                pb.end()
                pa.publish_arrays(np.ones((1, 1), np.float32),
                                  np.ones((1, 1), np.float32))
                pa.end()
            got = list(ca)
            assert len(got) == 1 and float(got[0].features[0, 0]) == 1.0
        finally:
            broker.stop()


_PRODUCER_SCRIPT = r"""
import sys
import numpy as np
from deeplearning4j_tpu.streaming import NDArrayPublisher

port, n_batches = int(sys.argv[1]), int(sys.argv[2])
rs = np.random.RandomState(3)
with NDArrayPublisher("127.0.0.1", port, "train") as pub:
    for i in range(n_batches):
        x = rs.randn(16, 4).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[rs.randint(0, 2, 16)]
        pub.publish_arrays(x, y)
    pub.end()
print("published", n_batches, flush=True)
"""


class TestCrossProcess:
    def test_fit_from_separate_producer_process(self, tmp_path):
        """The reference's end-to-end route: another PROCESS publishes
        NDArray minibatches to the broker while this process trains on
        the subscribed topic (CamelKafkaRouteBuilder semantics)."""
        n_batches = 12
        broker = StreamingBroker(port=0).start()
        try:
            route = NDArrayRoute("127.0.0.1", broker.port, "train")
            producer = subprocess.Popen(
                [sys.executable, "-c", _PRODUCER_SCRIPT,
                 str(broker.port), str(n_batches)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
            net = _net()
            net.fit(route.iterator())  # trains WHILE the producer runs
            out, err = producer.communicate(timeout=60)
            assert producer.returncode == 0, err
            assert f"published {n_batches}" in out
            assert net.iteration == n_batches
            assert np.isfinite(net.score_value)
        finally:
            broker.stop()


@pytest.mark.serving
class TestSlowSubscriber:
    """A slow consumer no longer stalls the topic forever: past the
    publish-patience window its frames are dropped (counted in
    ``broker.stats()``) and after ``drop_limit`` consecutive drops it is
    evicted — while a healthy subscriber keeps seeing every frame."""

    def test_drops_are_counted_and_persistent_laggard_evicted(self):
        n_frames = 20
        broker = StreamingBroker(port=0, subscriber_buffer=2, drop_limit=3,
                                 publish_patience_s=0.05).start()
        try:
            # a subscriber that handshakes, then never reads another byte;
            # big frames fill its socket buffer fast, then its queue
            slow = NDArrayConsumer("127.0.0.1", broker.port, "lag")
            fast_out = []
            fast = NDArrayConsumer("127.0.0.1", broker.port, "lag")
            t = threading.Thread(target=lambda: fast_out.extend(fast))
            t.start()
            big = np.zeros((64, 1024), np.float32)  # ~256 KB per frame
            labels = np.ones((64, 1), np.float32)
            with NDArrayPublisher("127.0.0.1", broker.port, "lag") as pub:
                for _ in range(n_frames):
                    pub.publish_arrays(big, labels)
                pub.end()
            t.join(30)
            st = broker.stats()
            assert st["frames_dropped"] > 0
            assert st["dropped_by_topic"].get("lag", 0) \
                == st["frames_dropped"]
            assert st["subscribers_disconnected"] == 1
            # the healthy subscriber missed nothing
            assert len(fast_out) == n_frames
            slow.close()
        finally:
            broker.stop()

    def test_fast_subscribers_never_drop(self):
        broker = StreamingBroker(port=0, subscriber_buffer=2, drop_limit=3,
                                 publish_patience_s=0.05).start()
        try:
            out = []
            cons = NDArrayConsumer("127.0.0.1", broker.port, "ok")
            t = threading.Thread(target=lambda: out.extend(cons))
            t.start()
            with NDArrayPublisher("127.0.0.1", broker.port, "ok") as pub:
                for i in range(10):
                    pub.publish_arrays(np.full((1, 2), i, np.float32),
                                       np.ones((1, 1), np.float32))
                pub.end()
            t.join(10)
            assert len(out) == 10
            st = broker.stats()
            assert st["frames_dropped"] == 0
            assert st["subscribers_disconnected"] == 0
        finally:
            broker.stop()


@pytest.mark.serving
class TestIdleTimeout:
    def test_silent_topic_raises_stream_stalled(self):
        """A consumer with an idle budget fails typed instead of hanging
        forever on a topic nobody publishes to."""
        broker = StreamingBroker(port=0).start()
        try:
            with NDArrayConsumer("127.0.0.1", broker.port, "dead",
                                 idle_timeout_s=0.3) as cons:
                start = time.monotonic()
                with pytest.raises(StreamStalled, match="dead"):
                    list(cons)
                assert time.monotonic() - start < 5.0
        finally:
            broker.stop()

    def test_timely_frames_do_not_stall(self):
        """The timeout is per-frame idle time, not total stream time: a
        stream longer than the budget flows as long as gaps stay under."""
        broker = StreamingBroker(port=0).start()
        try:
            cons = NDArrayConsumer("127.0.0.1", broker.port, "live",
                                   idle_timeout_s=2.0)
            out = []
            t = threading.Thread(target=lambda: out.extend(cons))
            t.start()
            with NDArrayPublisher("127.0.0.1", broker.port, "live") as pub:
                for i in range(5):
                    pub.publish_arrays(np.full((1, 2), i, np.float32),
                                       np.ones((1, 1), np.float32))
                    time.sleep(0.05)
                pub.end()
            t.join(10)
            assert len(out) == 5
        finally:
            broker.stop()


class TestLargeFrames:
    def test_multi_megabyte_batch_roundtrip(self):
        """Image-sized batches (a ~12 MB frame) survive framing and npz
        serde intact — length-prefixed frames, not line-based."""
        rs = np.random.RandomState(0)
        big = rs.randn(16, 224, 224, 3).astype(np.float32)  # ~9.6 MB
        labels = np.eye(10, dtype=np.float32)[rs.randint(0, 10, 16)]
        broker = StreamingBroker(port=0).start()
        try:
            with NDArrayConsumer("127.0.0.1", broker.port, "img") as c, \
                    NDArrayPublisher("127.0.0.1", broker.port, "img") as p:
                p.publish(DataSet(big, labels))
                p.end()
                got = list(c)
            assert len(got) == 1
            np.testing.assert_array_equal(got[0].features, big)
            np.testing.assert_array_equal(got[0].labels, labels)
        finally:
            broker.stop()
