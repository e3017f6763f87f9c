"""The main-path kernels compile for a TPU v5e, with no chip attached.

Interpret-mode tests cannot see what the TPU compiler refuses (unaligned
blocks, unsupported primitives); these compile each kernel, and the whole
prepared ResNet-50 as the one program the executor runs, for a described
``v5e:2x2`` topology at real widths and assert the Mosaic custom calls are
in the program.  The topology is described inside a fixture, never at import:
only the worker that runs this file loads the TPU library.
"""
import pathlib

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.workloads import init_graph_weights
from repro.kernels import ops
from repro.plan import ExecutionPlan, prepare_network, resnet50_graph

GOLDEN_RESNET50 = pathlib.Path(__file__).parent / "goldens" / \
    "plan_resnet50.json"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — any failure means: cannot here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A described-topology compile cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The ``ops`` wrappers pick interpret mode from the CPU backend this
    test runs on; steer them to the compiled kernels the chip would run."""
    monkeypatch.setattr(ops, "_interpret", lambda backend=None: False)


def _compile(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


@pytest.fixture(scope="module")
def resnet50_prepared():
    graph = resnet50_graph()
    plan = ExecutionPlan.from_json(GOLDEN_RESNET50.read_text())
    return prepare_network(plan, graph,
                           init_graph_weights(list(graph.layers)))


@pytest.fixture(scope="module")
def resnet50_steps(resnet50_prepared):
    """Each layer's static step and its prepared arrays, by layer name."""
    return {st.wl.name: (st, arr) for st, arr in
            zip(resnet50_prepared.steps, resnet50_prepared.arrays)}


@pytest.mark.parametrize("layer,k", [
    ("res50-l3-reduce", 256),     # tile asks for a 64-wide K block
    ("res50-l3-expand", 128),
    ("res50-l4-expand", 256),     # 8-block epilogue permutation
])
def test_rir_matmul_compiles_at_executor_blocks(one_chip, compiled_kernels,
                                                resnet50_steps, layer, k):
    st, arr = resnet50_steps[layer]
    assert st.k_width == k
    assert st.block_k % 128 == 0 or st.block_k == arr.w_eff.shape[0]
    rows = -(-st.rows_out // st.block_m) * st.block_m
    a = jax.ShapeDtypeStruct((rows, arr.w_eff.shape[0]), jnp.float32,
                             sharding=one_chip)
    b = jax.ShapeDtypeStruct(arr.w_eff.shape, jnp.float32, sharding=one_chip)
    perm = st.out_perm if len(st.out_perm) > 1 else None
    hlo = _compile(lambda a, b: ops.rir_matmul(
        a, b, perm, block_m=st.block_m, block_n=128, block_k=st.block_k),
        a, b)
    assert "tpu_custom_call" in hlo


def test_prepared_resnet50_compiles_as_one_program(one_chip,
                                                  compiled_kernels,
                                                  resnet50_prepared):
    """The whole prepared network is one program for the chip, with one
    Mosaic kernel per plan step inside it."""
    prepared = resnet50_prepared

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    arrays = jax.tree.map(spec, prepared.arrays)
    x = jax.ShapeDtypeStruct(prepared.input_shape, jnp.float32,
                             sharding=one_chip)
    hlo = prepared.program().lower(arrays, x).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == \
        len(prepared.plan.steps)


def test_gqa_decode_compiles_at_llama3p2_3b_widths(one_chip,
                                                   compiled_kernels):
    B, Hq, Hkv, D, S = 4, 24, 8, 128, 144     # prompt 128 + 16 generated
    spec = lambda *s: jax.ShapeDtypeStruct(  # noqa: E731
        s, jnp.bfloat16, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    hlo = _compile(ops.gqa_decode, spec(B, Hq, D), spec(B, Hkv, S, D),
                   spec(B, Hkv, S, D), lens)
    assert "tpu_custom_call" in hlo


def test_gqa_decode_compiles_at_a_nemotron_4_15b_head_shard(one_chip,
                                                          compiled_kernels):
    """One chip's share of Nemotron-4-15B over four: 12 query heads in
    groups of 6 over 2 KV heads of 128, a 640-position cache (prompt 512 +
    128 generated), batch 8."""
    B, Hq, Hkv, D, S = 8, 12, 2, 128, 640
    spec = lambda *s: jax.ShapeDtypeStruct(  # noqa: E731
        s, jnp.bfloat16, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    hlo = _compile(ops.gqa_decode, spec(B, Hq, D), spec(B, Hkv, S, D),
                   spec(B, Hkv, S, D), lens)
    assert "tpu_custom_call" in hlo


def test_linear_scan_compiles_at_rwkv6_1p6b_widths(one_chip,
                                                   compiled_kernels):
    B, H, T, dk = 1, 32, 256, 64              # d_inner 2048 = 32 heads x 64
    qkv = jax.ShapeDtypeStruct((B, H, T, dk), jnp.bfloat16,
                               sharding=one_chip)
    w = jax.ShapeDtypeStruct((B, H, T, dk), jnp.float32, sharding=one_chip)
    hlo = _compile(ops.linear_scan, qkv, qkv, qkv, w)
    assert "tpu_custom_call" in hlo


def test_unaligned_k_block_is_what_the_compiler_refuses(one_chip):
    """The rule the executor follows is the compiler's: a 64-wide K block
    over K=256 is refused, the 128-aligned block over the same K is not."""
    from repro.kernels.rir_matmul import rir_matmul_p
    a = jax.ShapeDtypeStruct((256, 256), jnp.float32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((256, 128), jnp.float32, sharding=one_chip)
    perm = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)

    def build(bk):
        return lambda a, b, p: rir_matmul_p(a, b, p, block_m=32, block_n=128,
                                            block_k=bk, interpret=False)

    assert "tpu_custom_call" in _compile(build(128), a, b, perm)
    with pytest.raises(Exception):
        _compile(build(64), a, b, perm)
