"""Metrics reporter + reporter-sampler tests.

Mirrors reference CruiseControlMetricsReporterTest (reporter produces real
metrics that the sampler consumes, SURVEY §4.5) fully in-process.
"""

import numpy as np
import pytest

from cruise_control_tpu.common.resources import Resource
from cruise_control_tpu.monitor.reporter_sampler import CruiseControlMetricsReporterSampler
from cruise_control_tpu.reporter import (
    BrokerMetric,
    InMemoryTransport,
    MetricSerde,
    MetricsRegistrySnapshotter,
    MetricsReporter,
    MetricType,
    PartitionMetric,
    TopicMetric,
)
from cruise_control_tpu.monitor.topology import BrokerNode, ClusterTopology, PartitionInfo


def test_serde_roundtrip():
    cases = [
        BrokerMetric(MetricType.BROKER_CPU_UTIL, 12345, 3, 0.75),
        TopicMetric(MetricType.TOPIC_BYTES_IN, 99, 1, 1024.5, topic="T0"),
        PartitionMetric(MetricType.PARTITION_SIZE, 7, 2, 5e6, topic="T1", partition=42),
    ]
    for m in cases:
        out = MetricSerde.deserialize(MetricSerde.serialize(m))
        assert out == m


def topo():
    brokers = (BrokerNode(0, "r0", "h0"), BrokerNode(1, "r1", "h1"))
    parts = (
        PartitionInfo("T0", 0, leader=0, replicas=(0, 1)),
        PartitionInfo("T0", 1, leader=0, replicas=(0, 1)),
        PartitionInfo("T0", 2, leader=1, replicas=(1, 0)),
    )
    return ClusterTopology(brokers=brokers, partitions=parts)


def test_reporter_to_sampler_pipeline():
    t = topo()
    transport = InMemoryTransport()

    def source_b0():
        return {
            "broker": {
                MetricType.BROKER_CPU_UTIL: 40.0,
                MetricType.BROKER_LOG_FLUSH_TIME_MS_MEAN: 5.0,
            },
            "topics": {"T0": {MetricType.TOPIC_BYTES_IN: 300.0,
                              MetricType.TOPIC_BYTES_OUT: 600.0}},
            "partitions": {("T0", 0): 1000.0, ("T0", 1): 2000.0},
        }

    reporter = MetricsReporter(
        MetricsRegistrySnapshotter(0, source_b0), transport, reporting_interval_ms=10
    )
    n = reporter.report_once(now_ms=1000)
    assert n == 6  # 2 broker + 2 topic + 2 partition records

    sampler = CruiseControlMetricsReporterSampler(transport, lambda: t)
    result = sampler.get_samples([], 0, 2000)
    # broker 0 leads T0-0 and T0-1
    assert len(result.partition_samples) == 2
    by_part = {s.entity.partition: s.values for s in result.partition_samples}
    md = sampler.metric_def
    nwin = md.metric_id("LEADER_BYTES_IN")
    disk = md.metric_id("DISK_USAGE")
    cpu = md.metric_id("CPU_USAGE")
    # byte attribution by size share: partition 1 is 2x partition 0
    assert by_part[1][nwin] == pytest.approx(200.0)
    assert by_part[0][nwin] == pytest.approx(100.0)
    assert by_part[0][disk] == 1000.0
    # CPU attribution sums to the broker CPU
    assert by_part[0][cpu] + by_part[1][cpu] == pytest.approx(40.0)
    # broker-only metrics surface as broker samples
    assert len(result.broker_samples) == 1
    bs = result.broker_samples[0]
    assert bs.values[md.metric_id("BROKER_LOG_FLUSH_TIME_MS_MEAN")] == 5.0
    # second poll: stream drained
    assert sampler.get_samples([], 0, 2000).partition_samples == []


# ---------------------------------------------------------------------------
# reference wire-format interop: records
# produced by the REFERENCE's in-broker plugin decode end-to-end
# ---------------------------------------------------------------------------

import struct

from cruise_control_tpu.reporter.metrics import (
    _REF_ID_BY_TYPE,
    _REF_TYPE_BY_ID,
    ReferenceMetricSerde,
)


def test_reference_serde_golden_bytes():
    """Hand-assembled frames per the reference's layouts:
    MetricSerde.java (class-id header), BrokerMetric.java:30-41,
    TopicMetric.java:37-52, PartitionMetric.java:44-60 — big-endian,
    value LAST, topic length an i32."""
    b = BrokerMetric(MetricType.BROKER_CPU_UTIL, 1234, 7, 0.5)
    expect_b = (
        b"\x00"                      # class id 0 = BROKER_METRIC
        + b"\x00"                    # version 0
        + b"\x05"                    # RawMetricType.BROKER_CPU_UTIL id 5
        + struct.pack(">q", 1234)
        + struct.pack(">i", 7)
        + struct.pack(">d", 0.5)
    )
    assert ReferenceMetricSerde.serialize(b) == expect_b
    assert ReferenceMetricSerde.deserialize(expect_b) == b

    t = TopicMetric(MetricType.TOPIC_BYTES_IN, 99, 1, 1024.5, topic="T0")
    expect_t = (
        b"\x01\x00\x02"              # class 1, version 0, TOPIC_BYTES_IN id 2
        + struct.pack(">q", 99) + struct.pack(">i", 1)
        + struct.pack(">i", 2) + b"T0"
        + struct.pack(">d", 1024.5)
    )
    assert ReferenceMetricSerde.serialize(t) == expect_t
    assert ReferenceMetricSerde.deserialize(expect_t) == t

    p = PartitionMetric(MetricType.PARTITION_SIZE, 7, 2, 5e6, topic="T1", partition=42)
    expect_p = (
        b"\x02\x00\x04"              # class 2, version 0, PARTITION_SIZE id 4
        + struct.pack(">q", 7) + struct.pack(">i", 2)
        + struct.pack(">i", 2) + b"T1"
        + struct.pack(">i", 42)
        + struct.pack(">d", 5e6)
    )
    assert ReferenceMetricSerde.serialize(p) == expect_p
    assert ReferenceMetricSerde.deserialize(expect_p) == p


def test_reference_id_table_complete_and_pinned():
    """All 63 reference RawMetricType ids (0-62) map; spot-pin ids straight
    from RawMetricType.java:27-97."""
    assert sorted(_REF_TYPE_BY_ID) == list(range(63))
    pins = {
        0: MetricType.ALL_TOPIC_BYTES_IN,
        2: MetricType.TOPIC_BYTES_IN,
        4: MetricType.PARTITION_SIZE,
        5: MetricType.BROKER_CPU_UTIL,
        19: MetricType.BROKER_REQUEST_HANDLER_AVG_IDLE_PERCENT,
        40: MetricType.BROKER_LOG_FLUSH_RATE,
        43: MetricType.BROKER_PRODUCE_REQUEST_QUEUE_TIME_MS_50TH,
        62: MetricType.BROKER_LOG_FLUSH_TIME_MS_999TH,
    }
    for ref_id, mt in pins.items():
        assert _REF_TYPE_BY_ID[ref_id] is mt
        assert _REF_ID_BY_TYPE[mt] == ref_id


def test_reference_serde_roundtrip_every_type():
    for ref_id, mt in _REF_TYPE_BY_ID.items():
        if mt.is_partition_scope:
            m = PartitionMetric(mt, 5, 1, 2.0, topic="t", partition=3)
        elif mt.is_topic_scope:
            m = TopicMetric(mt, 5, 1, 2.0, topic="t")
        else:
            m = BrokerMetric(mt, 5, 1, 2.0)
        assert ReferenceMetricSerde.deserialize(ReferenceMetricSerde.serialize(m)) == m


def test_reference_serde_skips_unknown_class_id():
    """A newer metric class decodes to None (reference fromBytes returns
    null), and the transport drops it instead of failing the poll."""
    frame = b"\x09" + b"\x00\x05" + struct.pack(">qid", 1, 1, 1.0)
    assert ReferenceMetricSerde.deserialize(frame) is None
    tr = InMemoryTransport(serde=ReferenceMetricSerde)
    tr.send(frame)
    tr.send(ReferenceMetricSerde.serialize(BrokerMetric(MetricType.BROKER_CPU_UTIL, 1, 0, 9.0)))
    polled = tr.poll()
    assert len(polled) == 1 and polled[0].value == 9.0


def test_reference_format_records_flow_into_aggregator():
    """End-to-end drop-in: reference-format records (as the reference's
    in-broker plugin produces them — including broker-INTERNAL metrics no
    process-external sidecar could observe) -> transport -> sampler ->
    windowed aggregator -> valid aggregated loads."""
    from cruise_control_tpu.monitor import (
        KAFKA_METRIC_DEF,
        WindowedMetricSampleAggregator,
    )

    t = topo()
    transport = InMemoryTransport(serde=ReferenceMetricSerde)
    assert transport.framed_native is False  # native columnar path is bypassed

    records = [
        BrokerMetric(MetricType.BROKER_CPU_UTIL, 500, 0, 40.0),
        # broker-internal metrics: the SlowBrokerFinder's inputs
        BrokerMetric(MetricType.BROKER_REQUEST_HANDLER_AVG_IDLE_PERCENT, 500, 0, 0.8),
        BrokerMetric(MetricType.BROKER_PRODUCE_LOCAL_TIME_MS_MEAN, 500, 0, 3.5),
        BrokerMetric(MetricType.BROKER_PRODUCE_LOCAL_TIME_MS_999TH, 500, 0, 25.0),
        TopicMetric(MetricType.TOPIC_BYTES_IN, 500, 0, 300.0, topic="T0"),
        TopicMetric(MetricType.TOPIC_BYTES_OUT, 500, 0, 600.0, topic="T0"),
        PartitionMetric(MetricType.PARTITION_SIZE, 500, 0, 1000.0, topic="T0", partition=0),
        PartitionMetric(MetricType.PARTITION_SIZE, 500, 0, 2000.0, topic="T0", partition=1),
    ]
    for m in records:
        transport.send(ReferenceMetricSerde.serialize(m))

    sampler = CruiseControlMetricsReporterSampler(transport, lambda: t)
    result = sampler.get_samples([], 0, 1000)
    assert len(result.partition_samples) == 2
    assert len(result.broker_samples) == 1
    md = sampler.metric_def
    bvals = result.broker_samples[0].values
    assert bvals[md.metric_id("BROKER_REQUEST_HANDLER_POOL_IDLE_PERCENT")] == pytest.approx(0.8)
    assert bvals[md.metric_id("BROKER_PRODUCE_LOCAL_TIME_MS_MEAN")] == pytest.approx(3.5)
    # percentile latency (reference reporter id-space 43-62) landed too
    assert bvals[md.metric_id("BROKER_PRODUCE_LOCAL_TIME_MS_999TH")] == pytest.approx(25.0)

    agg = WindowedMetricSampleAggregator(3, 1000, 1, KAFKA_METRIC_DEF)
    for s in result.partition_samples:
        assert agg.add_sample(s.entity, s.time_ms, s.values)
    # a second reporting round rolls the window forward so window 0 completes
    import dataclasses as _dc

    for m in records:
        transport.send(
            ReferenceMetricSerde.serialize(_dc.replace(m, time_ms=1500))
        )
    for s in CruiseControlMetricsReporterSampler(
        transport, lambda: t
    ).get_samples([], 1000, 2000).partition_samples:
        agg.add_sample(s.entity, s.time_ms, s.values)
    res = agg.aggregate()
    assert res.entity_valid.sum() == 2
    nwin = md.metric_id("LEADER_BYTES_IN")
    w0 = list(res.window_indices).index(0)
    # byte attribution by size share survived the reference wire format
    total_in = res.values[:, w0, nwin][res.entity_valid].sum()
    assert total_in == pytest.approx(300.0)
