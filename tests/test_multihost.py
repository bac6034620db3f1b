"""Multi-device scale-out lane: sharded serving == single-device serving.

Every test here needs 8 devices; normal single-CPU runs skip the whole
module, and the ``tier1-multidevice`` CI job provides them via
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (set in the job's
environment — it must land before the first jax import, so an in-test
``os.environ`` write is too late). On that runtime the schedulers place
their KV caches and params on a real ``(2, 4)`` ``(data, model)`` mesh
(``repro.launch.mesh.make_serve_mesh`` /
``repro.sharding.partition.cache_specs``), and the acceptance bar is the
same one every serving feature answers to: scores must match the
single-device drain (docs/sharding.md).
"""
import jax
import numpy as np
import pytest

from repro.data.requests import make_request_stream
from repro.data.synthetic import make_ctr_dataset
from repro.launch.mesh import make_serve_mesh
from repro.models.transformer import init_params
from repro.serve.scheduler import ServeScheduler
from repro.stream.publish import ParamPublisher, replicated_subscribers
from repro.stream.shard import fleet_serve_snapshot, shard_key

from test_serve import _cfg

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 devices: run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _reqs(cfg, *, n=4, seed=3, repeat_frac=0.25):
    ds = make_ctr_dataset(n_users=4, n_items=30, seq_len=10,
                          vocab_size=cfg.vocab_size)
    return make_request_stream(ds, n_requests=n, k=2, n_ctx=3, seed=seed,
                               repeat_frac=repeat_frac)


def _drain(params, cfg, reqs, *, mesh=None, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("capacity", 64)
    kw.setdefault("buckets", (8, 16))
    s = ServeScheduler(params, cfg, mesh=mesh, **kw)
    rids = [s.submit(r["context"], r["candidates"]) for r in reqs]
    out = s.run()
    return np.asarray([out[r].scores for r in rids]), s


class TestShardedEqualsUnsharded:
    """The 16-cell equivalence matrix: every serving configuration —
    decode impl x attention family x cache layout x KV dtype — must score
    identically (<= 1e-4) on the (2, 4) mesh and on one device. GSPMD may
    only reorder floating-point reductions; anything larger means a leaf
    was given a semantically-unsafe layout (the whole-head granularity
    rule of ``serve_param_specs`` exists because exactly that happened:
    sub-head sharding of the fused k projection drifted by ~1e-1)."""

    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    @pytest.mark.parametrize("layout", ["contiguous", "paged"])
    @pytest.mark.parametrize("attn_type", ["gqa", "mla"])
    @pytest.mark.parametrize("attn_impl", ["dense", "pallas"])
    def test_matrix(self, attn_impl, attn_type, layout, kv_dtype):
        cfg = _cfg(attn_type)
        params = init_params(jax.random.PRNGKey(0), cfg)
        reqs = _reqs(cfg)
        kw = dict(attn_impl=attn_impl, kv_dtype=kv_dtype,
                  paged=layout == "paged",
                  page_size=8 if layout == "paged" else 16)
        want, _ = _drain(params, cfg, reqs, **kw)
        got, sched = _drain(params, cfg, reqs,
                            mesh=make_serve_mesh(2, 4), **kw)
        np.testing.assert_allclose(got, want, atol=1e-4)
        tel = sched.telemetry()
        assert tel["mesh"] == {"data": 2, "model": 4}
        assert not tel["watchdog_fired"]

    def test_pool_pressure_on_sharded_slot_axis(self):
        """Eviction/adoption churn on the *sharded* global page pool: the
        reclamation paths move KV between slots that live on different
        data shards, and scores still match the single-device run."""
        cfg = _cfg()
        params = init_params(jax.random.PRNGKey(4), cfg)
        reqs = _reqs(cfg, n=10, seed=5, repeat_frac=0.3)
        kw = dict(paged=True, page_size=8, n_pages=10)
        want, _ = _drain(params, cfg, reqs, **kw)
        got, sched = _drain(params, cfg, reqs,
                            mesh=make_serve_mesh(2, 4), **kw)
        np.testing.assert_allclose(got, want, atol=1e-4)
        assert sched.telemetry()["page_evictions"] > 0


class TestFleetSwap:
    """Fleet semantics on the real mesh: replicated subscribers over one
    store, every shard draining before it swaps."""

    def test_fleet_wide_drain_before_swap_is_version_pure(self, tmp_path):
        """A publish landing while every shard has requests in flight must
        never mix weight versions inside one request, fleet-wide: each
        shard drains its in-flight work under the old params, then swaps
        (``drain_before_swap=True``), and its remaining queue scores under
        the new ones."""
        cfg = _cfg()
        p0 = init_params(jax.random.PRNGKey(0), cfg)
        p1 = init_params(jax.random.PRNGKey(1), cfg)
        mesh = make_serve_mesh(2, 4)
        reqs = _reqs(cfg, n=8, seed=7)

        pub = ParamPublisher(str(tmp_path))
        subs = replicated_subscribers(str(tmp_path), p0, 2, version=0)
        scheds = [ServeScheduler(p0, cfg, n_slots=2, capacity=64,
                                 buckets=(8, 16), mesh=mesh,
                                 drain_before_swap=True)
                  for _ in range(2)]
        rids = [[], []]
        for r in reqs:
            i = shard_key(r, 2)
            rids[i].append(scheds[i].submit(r["context"], r["candidates"]))
        for s in scheds:                 # work is genuinely in flight
            s.step()
            assert any(r.active for r in s._rows)
        pub.publish(1, p1)
        for s, sub in zip(scheds, subs):
            s.attach_param_source(sub.poll, poll_every=1)
        results = [s.run() for s in scheds]

        versions = []
        for res, ids in zip(results, rids):
            for rid in ids:
                vs = res[rid].params_versions
                assert len(vs) == 1, f"mixed versions {vs}"
                versions.append(vs[0])
        # the swap really happened on every shard (old AND new versions
        # served, each purely — the pre-publish params carry version None)
        # and the drains were counted
        assert {None, 1} <= set(versions)
        assert all(s.params_version == 1 for s in scheds)
        tel = fleet_serve_snapshot(scheds)
        assert tel["serve.swap_drains"]["value"] == 2
        assert tel["serve.swap_drain_steps"]["value"] >= 2
