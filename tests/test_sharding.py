"""Partition rules, divisibility guards, ZeRO-1 layout, HLO collective
parser, and the full 40-cell (smoke-scale) lower+compile sweep."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED, all_cells, get_arch
from repro.launch.hlo import collective_bytes, count_op
from repro.launch.mesh import make_cpu_mesh
from repro.launch.steps import build_cell
from repro.sharding.partition import (make_param_specs, rules_for,
                                      spec_for_shape, zero1_specs)


class TestSpecResolution:
    def _mesh(self):
        # 1-device mesh still carries axis names, so rule logic is exact
        return make_cpu_mesh()

    def test_divisibility_drop(self):
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        # sizes 1 always divide -> spec kept
        assert spec_for_shape((4, 8), (None, "model"), mesh) == P(None, "model")

    def test_right_alignment_for_scan_stack(self):
        mesh = self._mesh()
        # (L, d, f) with template (d, f) rules -> leading layer dim unsharded
        spec = spec_for_shape((12, 64, 128), (None, "model"), mesh)
        assert spec == P(None, None, "model")

    def test_lm_rules_match_expected_leaves(self):
        mesh = self._mesh()
        cfg = get_arch("qwen2-1.5b").smoke
        from repro.models.transformer import init_params
        shapes = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), cfg))
        specs = make_param_specs(shapes, rules_for("lm"), mesh)
        flat = {"/".join(str(k) for k in path): s for path, s in
                jax.tree_util.tree_flatten_with_path(specs)[0]}
        q_key = next(k for k in flat if "attn" in k and "'q'" in k
                     and "w" in k)
        assert flat[q_key].spec == P(None, None, "model")
        o_key = next(k for k in flat if "attn" in k and "'o'" in k
                     and "w" in k)
        assert flat[o_key].spec == P(None, "model", None)

    def test_zero1_adds_data_axis(self):
        mesh = self._mesh()
        shapes = {"w": jax.ShapeDtypeStruct((64, 128), jnp.float32)}
        base = {"w": NamedSharding(mesh, P(None, "model"))}
        z = zero1_specs(shapes, base, mesh)
        assert z["w"].spec == P("data", "model")

    def test_zero1_skips_fsdp_leaves(self):
        mesh = self._mesh()
        shapes = {"w": jax.ShapeDtypeStruct((64, 128), jnp.float32)}
        base = {"w": NamedSharding(mesh, P("data", "model"))}
        z = zero1_specs(shapes, base, mesh)
        assert z["w"].spec == P("data", "model")     # unchanged


class TestHLOParser:
    HLO = """
  %ag = bf16[16,256]{1,0} all-gather(%x), replica_groups=[32,16]<=[512], dimensions={0}
  %ar = f32[1024]{0} all-reduce(%y), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add
  %rs = f32[64]{0} reduce-scatter(%z), replica_groups=[2,8]<=[16], dimensions={0}
  %cp = bf16[32,32]{1,0} collective-permute(%w), source_target_pairs={{0,1}}
  %dot = f32[8,8]{1,0} dot(%a, %b)
"""

    def test_counts_and_bytes(self):
        out = collective_bytes(self.HLO, n_devices=16)
        assert out["count"] == 4
        ag = 16 * 256 * 2 * 15 / 16
        ar = 2 * 1024 * 4 * 3 / 4
        rs = 64 * 4 * 7
        cp = 32 * 32 * 2
        np.testing.assert_allclose(out["all-gather"], ag)
        np.testing.assert_allclose(out["all-reduce"], ar)
        np.testing.assert_allclose(out["reduce-scatter"], rs)
        np.testing.assert_allclose(out["collective-permute"], cp)
        np.testing.assert_allclose(out["total"], ag + ar + rs + cp)

    def test_tuple_shapes(self):
        hlo = "%t = (f32[8]{0}, bf16[4,4]{1,0}) all-reduce(%a, %b), replica_groups={{0,1}}\n"
        out = collective_bytes(hlo, n_devices=2)
        expect = 2 * (8 * 4 + 16 * 2) * 1 / 2
        np.testing.assert_allclose(out["all-reduce"], expect)

    def test_count_op(self):
        assert count_op(self.HLO, "all-gather") == 1
        assert count_op(self.HLO, "dot") == 1


class TestCellCompilation:
    """Every graded (arch x shape) cell must lower AND compile with its real
    sharded step fn — at smoke scale on the CPU mesh."""

    # ~40 XLA lower+compile invocations: excluded from the quick tier-1
    # loop (-m "not slow"); the tier1-multidevice lane runs it in full
    @pytest.mark.slow
    @pytest.mark.parametrize("arch,shape", all_cells())
    def test_cell_lowers_and_compiles(self, arch, shape):
        mesh = make_cpu_mesh()
        cell = build_cell(arch, shape, mesh, smoke=True)
        compiled = jax.jit(cell.step_fn,
                           donate_argnums=cell.donate).lower(
            *cell.args).compile()
        assert compiled.cost_analysis() is not None

    def test_train_cell_executes(self):
        mesh = make_cpu_mesh()
        cell = build_cell("qwen2-moe-a2.7b", "train_4k", mesh, smoke=True)

        def materialize(sds, c=[0]):
            c[0] += 1
            r = np.random.default_rng(c[0])
            if sds.dtype == jnp.int32:
                return jnp.asarray(r.integers(0, 4, sds.shape), jnp.int32)
            if sds.dtype == jnp.bool_:
                return jnp.asarray(r.random(sds.shape) < 0.5)
            return jnp.asarray(0.02 * r.normal(size=sds.shape), sds.dtype)

        state = jax.tree_util.tree_map(materialize, cell.args[0])
        batch = jax.tree_util.tree_map(materialize, cell.args[1])
        new_state, metrics = jax.jit(cell.step_fn)(state, batch)
        assert np.isfinite(float(metrics["loss"]))
