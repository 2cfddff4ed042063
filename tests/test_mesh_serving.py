"""Mesh-sharded serving tests.

Three layers:

* ``SERVE_RULES`` invariants — params never shard over (pod, data), KV
  cache/pool leaves shard only on ``cache_batch``, rules whose mesh
  axes are absent are dropped — checked on the degenerate host mesh, a
  forced-8-device serving mesh, and with no mesh at all;
* production/serving mesh factoring — shapes derive from the visible
  device count with clear errors instead of hardcoded-shape crashes;
* differential token exactness — the tensor-parallel engine (params
  placed with ``SERVE_RULES``, caches committed to per-replica
  submeshes) must reproduce the single-device token stream bit-for-bit
  on both the dense and paged substrates.

The forced-device tests need
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI mesh
lane); elsewhere they skip.
"""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from conftest import direct_greedy, tiny_model
from repro.distributed.sharding import (
    DEFAULT_RULES,
    SERVE_RULES,
    divisible_spec,
    param_shardings,
    replica_submeshes,
    serve_cache_spec,
)
from repro.launch.mesh import (
    make_host_mesh,
    make_production_mesh,
    make_serving_mesh,
)
from repro.serving import PipelineServer

N_DEV = jax.device_count()
forced8 = pytest.mark.skipif(
    N_DEV < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8",
)


def _param_specs(mesh):
    cfg, model, params = tiny_model()
    shardings = param_shardings(model.template, mesh, SERVE_RULES)
    return [
        s.spec for s in jax.tree_util.tree_leaves(shardings)
    ]


class TestServeRules:
    def test_embed_fsdp_dropped(self):
        """Serving has no FSDP: the vocab/embed gather must stay local."""
        assert DEFAULT_RULES["embed_fsdp"] == "data"
        assert SERVE_RULES["embed_fsdp"] is None

    def test_params_never_use_pod_or_data_host_mesh(self):
        for spec in _param_specs(make_host_mesh()):
            flat = {a for part in spec for a in (
                part if isinstance(part, tuple) else (part,)
            ) if part is not None}
            assert "data" not in flat and "pod" not in flat, spec

    @forced8
    def test_params_never_use_pod_or_data_forced_mesh(self):
        mesh = make_serving_mesh(model_axis=4, data_axis=2)
        for spec in _param_specs(mesh):
            flat = {a for part in spec for a in (
                part if isinstance(part, tuple) else (part,)
            ) if part is not None}
            assert "data" not in flat and "pod" not in flat, spec

    @forced8
    def test_params_do_use_model_axis(self):
        """Replication-only would vacuously pass the test above: at
        least one param leaf must actually shard over model."""
        mesh = make_serving_mesh(model_axis=4, data_axis=2)
        assert any("model" in tuple(spec) for spec in _param_specs(mesh))

    def test_cache_spec_masks_all_but_cache_batch_host(self):
        m = make_host_mesh()
        spec = serve_cache_spec(
            (4, 8, 64, 16), ("cache_batch", "kv_heads", "cache_seq", "head_dim"), m
        )
        assert all(a in (None, "data", ("pod", "data")) for a in tuple(spec))

    @forced8
    def test_cache_spec_masks_all_but_cache_batch_forced(self):
        mesh = make_serving_mesh(model_axis=4, data_axis=2)
        spec = serve_cache_spec(
            (4, 8, 64, 16), ("cache_batch", "kv_heads", "cache_seq", "head_dim"), mesh
        )
        # kv_heads would map to model under SERVE_RULES — masked out.
        assert "model" not in {
            a for part in tuple(spec)
            for a in (part if isinstance(part, tuple) else (part,))
        }

    def test_cache_spec_model_only_submesh_replicates(self):
        """No rule target for cache_batch on a model-only mesh: the
        whole leaf replicates inside the tensor-parallel device set."""
        mesh = Mesh(np.array(jax.devices()[:1]), ("model",))
        spec = serve_cache_spec(
            (4, 8, 64, 16), ("cache_batch", "kv_heads", "cache_seq", "head_dim"), mesh
        )
        assert spec == P(None, None, None, None) or spec == P()

    @forced8
    def test_engine_committed_cache_sharding(self):
        """The live engine's caches carry serve_cache_spec shardings:
        the slot axis maps to the owning slice's (size-1) data axis and
        no cache leaf ever shards over model."""
        cfg, model, params = tiny_model()
        mesh = make_serving_mesh(model_axis=4, data_axis=2)
        server = PipelineServer(
            model, params, mesh=mesh, n_groups=2, n_replicas=2,
            policy="uniform", max_len=64, max_batch=4, seed=3,
        )
        for (g, r), cache in server._caches.items():
            for leaf in jax.tree_util.tree_leaves(cache):
                spec = tuple(leaf.sharding.spec)
                flat = {
                    a for part in spec
                    for a in (part if isinstance(part, tuple) else (part,))
                    if a is not None
                }
                assert "model" not in flat, (g, r, spec)
                if spec:  # leading slot dim == cache_batch -> data
                    assert spec[0] == "data", (g, r, spec)

    def test_absent_mesh_axes_dropped_no_mesh_axis(self):
        """Rules referencing axes the mesh lacks resolve to replication."""
        mesh = Mesh(np.array(jax.devices()[:1]), ("model",))
        # batch -> ("pod", "data"): neither exists on a model-only mesh.
        spec = divisible_spec((8, 16), ("batch", "embed"), mesh, SERVE_RULES)
        assert spec == P(None, None) or spec == P()


class TestMeshFactoring:
    def test_production_mesh_derives_from_device_count(self):
        mesh = make_production_mesh()
        assert mesh.axis_names == ("data", "model")
        assert mesh.devices.size == N_DEV

    def test_production_mesh_shape_too_big_errors(self):
        with pytest.raises(ValueError, match="xla_force_host_platform_device_count"):
            make_production_mesh(shape=(N_DEV + 1, 2))

    def test_production_mesh_explicit_shape(self):
        mesh = make_production_mesh(shape=(1, 1))
        assert mesh.axis_names == ("data", "model")

    def test_multi_pod_odd_count_errors(self):
        if N_DEV % 2 == 0:
            mesh = make_production_mesh(multi_pod=True)
            assert mesh.axis_names == ("pod", "data", "model")
        else:
            with pytest.raises(ValueError, match="even device count"):
                make_production_mesh(multi_pod=True)

    def test_serving_mesh_too_big_errors(self):
        with pytest.raises(ValueError, match="xla_force_host_platform_device_count"):
            make_serving_mesh(model_axis=N_DEV + 1, data_axis=1)

    def test_serving_mesh_bad_data_axis(self):
        with pytest.raises(ValueError, match="data_axis"):
            make_serving_mesh(model_axis=1, data_axis=0)

    @forced8
    def test_serving_mesh_forced_shape(self):
        mesh = make_serving_mesh(model_axis=4, data_axis=2)
        assert mesh.devices.shape == (2, 4)
        assert mesh.axis_names == ("data", "model")


class TestReplicaSubmeshes:
    def test_host_mesh_single_slice_round_robin(self):
        slices, slice_of = replica_submeshes(make_host_mesh(), 3)
        assert len(slices) == 1 and slice_of == [0, 0, 0]
        assert slices[0].axis_names == ("data", "model")

    def test_rejects_foreign_axes(self):
        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("pod", "model"))
        with pytest.raises(ValueError, match="data"):
            replica_submeshes(mesh, 2)

    @forced8
    def test_forced_slices_are_disjoint(self):
        mesh = make_serving_mesh(model_axis=4, data_axis=2)
        slices, slice_of = replica_submeshes(mesh, 3)
        assert len(slices) == 2 and slice_of == [0, 1, 0]
        d0 = {d.id for d in slices[0].devices.flat}
        d1 = {d.id for d in slices[1].devices.flat}
        assert d0.isdisjoint(d1) and len(d0) == len(d1) == 4


def _drain(server, reqs, limit=5000):
    for _ in range(limit):
        if all(r.done or r.dropped for r in reqs):
            return [list(r.generated) for r in reqs]
        server.step()
    raise RuntimeError("did not drain")


def _streams(model, params, cfg, *, mesh, paged, n_tokens=5):
    server = PipelineServer(
        model,
        params,
        mesh=mesh,
        n_groups=2,
        n_replicas=2,
        policy="uniform",
        max_len=64,
        max_batch=4,
        paged=paged,
        page_size=8,
        seed=3,
    )
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n) for n in (5, 9, 7)]
    return _drain(server, [server.submit(p, n_tokens=n_tokens) for p in prompts])


@forced8
class TestMeshDifferential:
    @pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
    def test_tensor_parallel_token_exact(self, paged):
        """data=2 x model=4: two real replica device sets, each stage
        one jitted TP dispatch — streams must match single-device."""
        cfg, model, params = tiny_model()
        ref = _streams(model, params, cfg, mesh=None, paged=paged)
        mesh = make_serving_mesh(model_axis=4, data_axis=2)
        got = _streams(model, params, cfg, mesh=mesh, paged=paged)
        assert got == ref

    @pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
    def test_pallas_kernels_shard_over_heads(self, paged):
        """XLA cannot partition a Mosaic kernel: under the mesh the
        attention kernels shard_map themselves over heads, and the
        streams still match single-device."""
        import dataclasses

        from repro.models import build_model

        cfg, _, params = tiny_model()
        model = build_model(dataclasses.replace(cfg, attn_impl="pallas"))
        ref = _streams(model, params, cfg, mesh=None, paged=paged)
        mesh = make_serving_mesh(model_axis=4, data_axis=2)
        assert _streams(model, params, cfg, mesh=mesh, paged=paged) == ref

    def test_failover_on_mesh_token_exact(self):
        cfg, model, params = tiny_model()
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab_size, size=n) for n in (5, 9, 7)]

        def run(mesh, kill):
            server = PipelineServer(
                model, params, mesh=mesh, n_groups=2, n_replicas=2,
                policy="uniform", max_len=64, max_batch=4, seed=3,
            )
            reqs = [server.submit(p, n_tokens=6) for p in prompts]
            if kill:
                for _ in range(3):
                    server.step()
                server.fail_replica(0, 0)
            return _drain(server, reqs)

        ref = run(None, kill=False)
        assert run(make_serving_mesh(model_axis=4, data_axis=2), kill=True) == ref
