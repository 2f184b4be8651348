"""Tensor-parallel mesh-sharded paged decode tests (parallel/mesh.py +
``GenerationServer(mesh=/tp=)``).

Covers the tp>1 serving contract on the CPU mesh (8 forced virtual
devices): loud typed geometry validation (device divisibility, head
divisibility, axis naming — ``MeshGeometryError`` before any thread
starts), greedy and sampled bit-parity with the single-chip path at
tp=2 and tp=4 for f32 and int8 pools, the Pallas backend fed per-shard
head counts, ZERO decode recompiles under occupancy churn on the mesh
path, cross-TP snapshot handoff (export at tp=2, adopt at tp=4 and
tp=1) resuming bit-exactly, replica-group fleets (2 groups x tp=2) with
a mid-stream kill losing zero futures, and the hand-over contract: a
mesh server writes nothing on its net, so a single-chip server on the
same net traces beside it.
"""

import time

import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo import (TransformerLM, greedy_generate,
                                           sample_generate)
from deeplearning4j_tpu.parallel.fleet import ReplicaFleet, device_groups
from deeplearning4j_tpu.parallel.generation import GenerationServer
from deeplearning4j_tpu.parallel.handoff import adopt_request
from deeplearning4j_tpu.parallel.mesh import (MODEL_AXIS, MeshGeometryError,
                                              model_mesh)
from deeplearning4j_tpu.parallel.resilience import (ChaosPolicy,
                                                    ResilienceError)
from tests.serving_helpers import (GREEDY, SAMPLED, V, serving, tiny_lm,
                                   wait_replica_midstream)

pytestmark = pytest.mark.mesh


@pytest.fixture(scope="module")
def lm():
    """Four heads so the pool shards cleanly at tp=2 AND tp=4."""
    return tiny_lm(n_heads=4, seed=5)


@pytest.fixture(scope="module")
def refs(lm):
    """Serial single-chip references, computed while no server is live
    (the existing generation suite pins the tp=1 server to these
    bit-exactly, so parity against them IS parity against the
    single-chip serving path)."""
    return {
        "greedy": greedy_generate(lm, GREEDY[0][None], GREEDY[1], V)[0],
        "sampled": sample_generate(lm, SAMPLED[0][None], SAMPLED[1], V,
                                   temperature=SAMPLED[2],
                                   top_k=SAMPLED[3], seed=SAMPLED[4])[0],
    }


def _serve_one(lm, spec, **kw):
    p, steps, temp, top_k, seed = spec
    with serving(lm, V, slots=2, page_size=4, **kw) as srv:
        fut = srv.submit(p, steps, temperature=temp, top_k=top_k,
                         seed=seed)
        return np.asarray(fut.result(timeout=180))


class TestMeshGeometry:
    """Every bad geometry fails typed and LOUD, naming the numbers."""

    def test_model_mesh_validation(self):
        import jax
        ndev = len(jax.devices())
        assert ndev == 8, "conftest forces 8 virtual CPU devices"
        with pytest.raises(MeshGeometryError, match=">= 1"):
            model_mesh(0)
        with pytest.raises(MeshGeometryError, match="exceeds"):
            model_mesh(ndev + 1)
        with pytest.raises(MeshGeometryError, match="not divisible"):
            model_mesh(3)
        m = model_mesh(2)
        assert m.shape[MODEL_AXIS] == 2

    def test_device_groups_disjoint_and_validated(self):
        import jax
        groups = device_groups(2, 2)
        assert len(groups) == 2 and all(len(g) == 2 for g in groups)
        assert len({d.id for g in groups for d in g}) == 4  # disjoint
        with pytest.raises(MeshGeometryError):
            device_groups(0, 2)
        with pytest.raises(MeshGeometryError):
            device_groups(3, 4, devices=jax.devices())  # 12 > 8

    def test_heads_not_divisible_by_tp(self):
        net = TransformerLM(num_labels=V, max_length=16, d_model=16,
                            n_heads=2, n_blocks=1, seed=7).init()
        with pytest.raises(MeshGeometryError, match="not divisible"):
            GenerationServer(net, V, slots=2, tp=4)

    def test_tp_disagrees_with_mesh(self, lm):
        with pytest.raises(MeshGeometryError, match="disagrees"):
            GenerationServer(lm, V, slots=2, mesh=model_mesh(2), tp=4)

    def test_mesh_without_model_axis(self, lm):
        import jax
        from jax.sharding import Mesh
        data_only = Mesh(np.array(jax.devices()[:2]), ("data",))
        with pytest.raises(MeshGeometryError, match="model"):
            GenerationServer(lm, V, slots=2, mesh=data_only)


@pytest.mark.generation
@pytest.mark.allow_output_recompiles
class TestMeshParity:
    """The tentpole invariant: sharding the page pool head-parallel
    changes WHERE the KV lives, never a single output bit. The only
    collective is an exact all-gather of disjoint per-head contexts
    before the replicated output projection."""

    def test_tp2_greedy_and_sampled_bitexact(self, lm, refs):
        # one server, both sampling modes: greedy and sampled share the
        # sharded decode programs, so a second server would only re-pay
        # the probe+warmup cost
        with serving(lm, V, slots=2, page_size=4, tp=2) as srv:
            for name, spec in (("greedy", GREEDY), ("sampled", SAMPLED)):
                p, steps, temp, top_k, seed = spec
                fut = srv.submit(p, steps, temperature=temp,
                                 top_k=top_k, seed=seed)
                np.testing.assert_array_equal(
                    np.asarray(fut.result(timeout=180)), refs[name])

    def test_tp4_greedy_bitexact(self, lm, refs):
        out = _serve_one(lm, GREEDY, tp=4)
        np.testing.assert_array_equal(out, refs["greedy"])

    def test_tp_int8_parity_with_single_chip_int8(self, lm):
        """int8 scale planes shard on the same head axis as the pages;
        quantized mesh decode matches single-chip int8 exactly. (tp=4
        int8 is covered by the cross-TP handoff test, which adopts into
        an int8 tp=4 server.)"""
        base = _serve_one(lm, GREEDY, kv_dtype="int8")
        out = _serve_one(lm, GREEDY, tp=2, kv_dtype="int8")
        np.testing.assert_array_equal(out, base)

    @pytest.mark.pallas
    def test_tp2_pallas_backend_bitexact(self, lm, refs, monkeypatch):
        """The Pallas kernel sees only its LOCAL head shard (grid
        ``(B, H/tp, NP)``) — shard_map hands it per-shard operands with
        no kernel changes. The programs must really be traced through
        the layer's shard_map path: interpreted, the kernel is plain XLA
        that GSPMD partitions without it, but a Mosaic kernel cannot be
        partitioned automatically (first seen on four real chips)."""
        from deeplearning4j_tpu.nn.conf.layers.attention import (
            SelfAttentionLayer)
        calls = []
        orig = SelfAttentionLayer._sharded_write_attend
        monkeypatch.setattr(
            SelfAttentionLayer, "_sharded_write_attend",
            lambda self, *a: calls.append(1) or orig(self, *a))
        out = _serve_one(lm, GREEDY, tp=2, paged_attention="pallas")
        np.testing.assert_array_equal(out, refs["greedy"])
        assert calls, "tp=2 programs were traced as single-chip config"


@pytest.mark.generation
class TestMeshScheduling:
    def test_no_recompile_on_occupancy_churn_tp2(self):
        """The zero-retrace property survives sharding: the mesh-keyed
        decode program, one prefill bucket and the COW page-copy warm
        up ONCE, and arbitrary occupancy churn adds ZERO compiled
        programs — block tables and positions stay data on the mesh
        path too."""
        net = TransformerLM(num_labels=V, max_length=16, d_model=8,
                            n_heads=2, n_blocks=1, seed=9).init()
        rs = np.random.RandomState(0)
        with serving(net, V, slots=3, min_prefill_bucket=4,
                     tp=2) as srv:
            base = len(net._output_cache)
            warm = [srv.submit(rs.randint(0, V, 3), 5),
                    srv.submit(rs.randint(0, V, 7), 2)]
            for f in warm:
                f.result(timeout=180)
            warmed = len(net._output_cache)
            assert warmed - base == 3

            churn = [(4, 3), (2, 7), (6, 1), (8, 4), (3, 2), (5, 6)]
            futs = []
            for plen, mt in churn:
                futs.append(srv.submit(rs.randint(0, V, plen), mt))
                time.sleep(0.02)  # stagger: arrive at varied occupancy
            for f, (_plen, mt) in zip(futs, churn):
                assert f.result(timeout=180).shape == (mt,)
            assert len(net._output_cache) == warmed
            st = srv.stats()
        assert st["completed"] == 8
        assert st["decode_steps"] > 0


def _snap_at_tp(lm, spec, tp, **kw):
    p, steps, temp, top_k, seed = spec
    with serving(lm, V, slots=2, page_size=4, snapshot_every=4,
                 steps_per_dispatch=2, tp=tp, **kw) as srv:
        fut = srv.submit(p, steps, temperature=temp, top_k=top_k,
                         seed=seed)
        out = np.asarray(fut.result(timeout=180))
    snap = getattr(fut, "_kv_snapshot", None)
    assert snap is not None, "snapshot_every published no snapshot"
    return out, snap


@pytest.mark.handoff
@pytest.mark.allow_output_recompiles
class TestCrossTPHandoff:
    """The v3 wire contract end to end: export gathers the sharded pool
    to ONE canonical host layout, adopt re-shards to whatever mesh the
    adopting server runs — tp=2 -> tp=4 and tp=2 -> tp=1 resume at
    position N bit-exactly."""

    @pytest.mark.parametrize("kv_dtype", [None, "int8"],
                             ids=["f32", "int8"])
    def test_tp2_export_adopts_at_tp4_and_tp1(self, lm, kv_dtype):
        for spec, dsts in ((GREEDY, (4, 1)), (SAMPLED, (4,))):
            # greedy covers both re-shard directions; sampled pins the
            # RNG schedule across the upshard (the downshard path is
            # spec-independent once greedy has proven it)
            out, snap = _snap_at_tp(lm, spec, tp=2, kv_dtype=kv_dtype)
            assert snap.version == 3
            assert snap.shards == 2          # exporter geometry, FYI
            assert snap.head_layout == "canonical"
            assert 0 < snap.count < spec[1]  # genuinely mid-stream
            for tp_dst in dsts:
                with serving(lm, V, slots=2, page_size=4, tp=tp_dst,
                             kv_dtype=kv_dtype) as dst:
                    res = adopt_request(dst, snap).result(timeout=180)
                    st = dst.stats()["handoff"]
                np.testing.assert_array_equal(np.asarray(res), out)
                assert st["resumes"] == 1 and st["fallbacks"] == 0


@pytest.mark.fleet
@pytest.mark.allow_output_recompiles
class TestMeshFleet:
    def test_replica_groups_midstream_kill_zero_lost(self, lm):
        """Two replica GROUPS of two devices each behind one fleet —
        each replica is a whole tp=2 mesh server on a disjoint device
        subset. A mid-stream group kill harvests snapshots and the
        surviving group finishes every stream bit-exactly: zero lost
        futures on the ledger."""
        groups = device_groups(2, 2)
        rng = np.random.default_rng(31)
        specs = []
        for i in range(6):
            p = rng.integers(1, V, size=3 + i % 3).astype(np.int64)
            specs.append((p, 8, 0.0, 0, 0) if i % 2 == 0
                         else (p, 8, 0.9, 5, 3000 + i))
        refs = []
        for p, steps, temp, top_k, seed in specs:
            refs.append(greedy_generate(lm, p[None], steps, V)[0]
                        if temp == 0.0 else
                        sample_generate(lm, p[None], steps, V,
                                        temperature=temp, top_k=top_k,
                                        seed=seed)[0])

        def factory(rid):
            mesh = model_mesh(2, devices=groups[rid % len(groups)])
            chaos = ChaosPolicy(seed=1000 + rid, stall_rate=1.0,
                                stall_s=0.005)
            return GenerationServer(lm, V, slots=4, page_size=4,
                                    snapshot_every=1,
                                    steps_per_dispatch=1,
                                    mesh=mesh, chaos=chaos)

        fl = ReplicaFleet(factory, replicas=2, max_pending=64,
                          restart_backoff_s=0.02)
        try:
            futs = []
            for p, steps, temp, top_k, seed in specs:
                t_end = time.monotonic() + 60.0
                while True:
                    try:
                        futs.append(fl.submit(
                            p, steps, temperature=temp, top_k=top_k,
                            seed=seed, deadline_s=300.0))
                        break
                    except ResilienceError:
                        assert time.monotonic() < t_end
                        time.sleep(0.02)
            wait_replica_midstream(fl, 0, min_snapshots=2, min_active=1,
                                   timeout=120.0)
            fl.kill_replica(0)
            outs = [f.result(timeout=600) for f in futs]
            st = fl.stats()
        finally:
            fl.close()
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(np.asarray(got), ref)
        assert st["completed"] == len(specs)
        assert st["failed"] == 0 and st["expired"] == 0
        assert st["deaths"] >= 1


@pytest.mark.generation
@pytest.mark.allow_output_recompiles
class TestRestoreOnClose:
    def test_close_restores_net_level_mesh_knobs(self, lm, refs):
        """There is nothing to restore: a server hands its mesh and its
        read backend to the layers in the carry of each program it traces
        and writes nothing on the net. So a ``tp=2`` server and a
        single-chip server on the SAME net, with different backends,
        trace their first dispatches at the same time from two threads
        and each serves bit-identically to the reference; the layers
        carry neither fact before, between or after, and no lock orders
        the traces."""
        import dataclasses
        import threading

        attn = [lyr for _n, lyr in lm._stream_layers()
                if hasattr(lyr, "init_paged_carry")]
        assert attn, "TransformerLM exposes its paged attention layers"

        def untouched():
            for lyr in attn:
                assert not hasattr(lyr, "paged_mesh")
                assert "paged_attention" not in {
                    f.name for f in dataclasses.fields(lyr)}
            assert not hasattr(GenerationServer, "_trace_lock")

        untouched()
        outs, start = {}, threading.Barrier(2)

        def first_dispatch(name, srv):
            start.wait(timeout=60)
            outs[name] = np.asarray(
                srv.submit(GREEDY[0], GREEDY[1]).result(timeout=180))

        with serving(lm, V, slots=2, page_size=4, tp=2,
                     paged_attention="pallas") as mesh_srv, \
                serving(lm, V, slots=2, page_size=4,
                        paged_attention="xla") as chip_srv:
            assert mesh_srv._mesh is not None and chip_srv._mesh is None
            assert (mesh_srv._pa, chip_srv._pa) == ("pallas", "xla")
            threads = [threading.Thread(target=first_dispatch, args=a)
                       for a in (("mesh", mesh_srv), ("chip", chip_srv))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=240)
            assert not any(t.is_alive() for t in threads)
            untouched()
        for name in ("mesh", "chip"):
            np.testing.assert_array_equal(outs[name], refs["greedy"])
        untouched()
