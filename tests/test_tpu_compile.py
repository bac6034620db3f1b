"""Ahead-of-time compiles of the main path's Pallas kernels for a TPU v5e.

Nothing runs: each test lowers a kernel at real widths with ``interpret=False``
and the block sizes ``repro.kernels.autotune`` picks on a TPU, and compiles
it with the TPU compiler against a described (not attached) ``v5e:2x2``
topology. That is where Mosaic refuses a block layout, a slice or a VMEM
budget that the CPU interpreter accepts. Each compiled program must hold the
Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, so with several test workers the
others must still collect these tests and only the one that runs them
loads it.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.windowed import ResetConfig
from repro.kernels.autotune import decode_block, train_block
from repro.kernels.decode_attn.ops import decode_attention
from repro.kernels.windowed_attn.ops import windowed_attention

# Qwen2-1.5B attention (configs/qwen2_1_5b.py)
H, HK, D = 12, 2, 128
# MiniCPM3-4B absorbed MLA (configs/minicpm3_4b.py): latent 256 + rope 32
H_MLA, R_KV, D_ROPE = 40, 256, 32
BF16, I8, I32, F32 = jnp.bfloat16, jnp.int8, jnp.int32, jnp.float32


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["tpu", "interpret"])
@pytest.mark.parametrize("kind, size, dim, on_tpu", [
    ("train", 1536, 128, 512),           # the benchmark cell's rows
    ("train", 256, 128, 256),
    ("decode", 2400, 128, 512),
    ("decode", 256, 64, 256),
])
def test_autotune_table_pins(kind, size, dim, on_tpu, interpret):
    """The block table's values: a TPU block is a deliberate change of this
    table; interpret mode keeps the historic 256 (train) and 64 (decode)."""
    if kind == "train":
        got, interpreted = train_block(size, dim, interpret=interpret), 256
    else:
        got, interpreted = decode_block(size, dqk=dim, dv=dim,
                                        interpret=interpret), 64
    assert got == (interpreted if interpret else on_tpu)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


# ---- windowed DTI attention (training) ---------------------------------

B, S, WINDOW = 2, 512, 256


def _windowed(q, k, v, qn, kn, v0, pos, is_sum, valid, seg):
    return windowed_attention(
        q, k, v, pos_q=pos, pos_k=pos, window=WINDOW, is_sum_q=is_sum,
        is_sum_k=is_sum, valid_k=valid, seg_q=seg, seg_k=seg, q_nope=qn,
        k_nope=kn, alibi=jnp.linspace(0.1, 0.5, H), v0=v0,
        reset=ResetConfig(0.0, 0.3, WINDOW / 2),
        block_size=train_block(S, D, interpret=False), interpret=False)


_WIN_SHAPES = [((B, S, H, D), BF16), ((B, S, HK, D), BF16),
               ((B, S, HK, D), BF16), ((B, S, H, D), BF16),
               ((B, S, HK, D), BF16), ((B, S, HK, D), BF16),
               ((B, S), I32), ((B, S), I32), ((B, S), I32), ((B, S), I32)]


def test_windowed_forward_compiles(one_chip):
    _compile(_windowed, _WIN_SHAPES, one_chip)


def test_windowed_backward_compiles(one_chip):
    def grads(q, k, v, qn, kn, v0, *rest):
        def loss(*xs):
            return _windowed(*xs, *rest).astype(F32).sum()
        return jax.grad(loss, argnums=tuple(range(6)))(q, k, v, qn, kn, v0)

    text = _compile(grads, _WIN_SHAPES, one_chip)
    assert text.count("tpu_custom_call") >= 3          # fwd, dq, dk/dv


def test_windowed_ragged_length_compiles(one_chip):
    """S that is not a block multiple pads instead of shrinking the block."""
    s = 192
    shapes = [((1, s, H, D), BF16), ((1, s, HK, D), BF16),
              ((1, s, HK, D), BF16), ((1, s), I32)]

    def fn(q, k, v, pos):
        return windowed_attention(q, k, v, pos_q=pos, pos_k=pos, window=64,
                                  block_size=train_block(s, D,
                                                         interpret=False),
                                  interpret=False)
    _compile(fn, shapes, one_chip)


# ---- decode attention (serving) ------------------------------------------

NB, NS, CAP = 4, 16, 1024


def _ids():
    return [((NB, NS), I32), ((NB, CAP), I32), ((NB, NS), I32),
            ((NB, NS), I32), ((NB, CAP), I32)]


def _decode(h, alibi_heads, scale=None, rope_start=0, quant=False):
    def fn(q, k, v, qn, *rest):
        if quant:
            ks, vs, pq, pk, isum, sq, sk = rest
            extra = dict(k_scale=ks, v_scale=vs, rope_start=rope_start)
        else:
            kn, pq, pk, isum, sq, sk = rest
            extra = dict(k_nope=kn)
        return decode_attention(
            q, k, v, pq, pk, window=0, is_sum_q=isum, q_nope=qn,
            alibi=jnp.linspace(0.1, 0.5, alibi_heads), seg_q=sq, seg_k=sk,
            scale=scale, interpret=False, **extra)
    return fn


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_decode_gqa_compiles(one_chip, quant):
    kv = I8 if quant else BF16
    shapes = [((NB, NS, H, D), BF16), ((NB, CAP, HK, D), kv),
              ((NB, CAP, HK, D), kv), ((NB, NS, H, D), BF16)]
    shapes += ([((NB, CAP, HK, 1), F32), ((NB, CAP, HK), F32)] if quant
               else [((NB, CAP, HK, D), BF16)])
    assert decode_block(CAP, dqk=D, dv=D, interpret=False) % 128 == 0
    _compile(_decode(H, H, quant=quant), shapes + _ids(), one_chip)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_decode_mla_compiles(one_chip, quant):
    kv = I8 if quant else BF16
    dqk = R_KV + D_ROPE
    shapes = [((NB, NS, H_MLA, dqk), BF16), ((NB, CAP, 1, dqk), kv),
              ((NB, CAP, 1, R_KV), kv), ((NB, NS, H_MLA, dqk), BF16)]
    shapes += ([((NB, CAP, 1, 2), F32), ((NB, CAP, 1), F32)] if quant
               else [((NB, CAP, 1, dqk), BF16)])
    _compile(_decode(H_MLA, H_MLA, scale=dqk ** -0.5, rope_start=R_KV,
                     quant=quant), shapes + _ids(), one_chip)


def test_decode_sharded_over_mesh_compiles(topo):
    """On a (data, model) mesh the kernel runs per shard under shard_map:
    GSPMD refuses to partition a Mosaic kernel by itself."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    kv_spec = NamedSharding(mesh, P("data", None, "model", None))
    rep = NamedSharding(mesh, P())

    def fn(q, k, v, pq, pk):
        return decode_attention(q, k, v, pq, pk, window=0, interpret=False,
                                mesh=mesh)

    args = [jax.ShapeDtypeStruct((NB, NS, H, D), BF16, sharding=kv_spec),
            jax.ShapeDtypeStruct((NB, CAP, HK, D), BF16, sharding=kv_spec),
            jax.ShapeDtypeStruct((NB, CAP, HK, D), BF16, sharding=kv_spec),
            jax.ShapeDtypeStruct((NB, NS), I32, sharding=rep),
            jax.ShapeDtypeStruct((NB, CAP), I32, sharding=rep)]
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()
