"""The round-path Pallas kernels compile for a TPU v5e at real shapes.

Nothing runs: each case lowers a kernel entry point with
`interpret=False` for a described (not attached) v5e chip and compiles
it with the TPU compiler installed here, so a block shape, cast, gather
or layout that Mosaic refuses fails this file instead of a chip run.
Shapes are the federation's own: mnist-cnn's parameter count for LSH,
selection on each side of the one-shot/tiled and exact/ANN switches,
and the paper's exchange shape (M=16 after padding, N=9, R=64, C=10).

The local update's chunked form (`protocol._chunked_local_update` at
the width `update_chunk` gives a TPU) compiles too, at the benchmark's
m1024 TCN shapes and at M=10 (a remainder chunk), within a few GB of
scratch memory. So does the personal exchange's mapped forwards
(`protocol._mapped_ref_logits` at the width `lane_chunk` gives a
TPU), at the benchmark's m128 and m10 CNN shapes.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU compiler's library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BITS = 256
WORDS = BITS // 32
NEIGHBORS = 12


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture(scope="module")
def sds(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _mnist_cnn_padded_p():
    from repro.configs.paper_models import mnist_cnn
    from repro.kernels.lsh_projection import CHUNK
    from repro.models import init_client_model
    shapes = jax.eval_shape(lambda k: init_client_model(mnist_cnn(), k),
                            jax.random.PRNGKey(0))
    p = sum(leaf.size for leaf in jax.tree.leaves(shapes))
    return p + (-p) % CHUNK


def _lsh(sds):
    from repro.kernels.lsh_projection import lsh_project_sums_batched
    p = _mnist_cnn_padded_p()
    assert p > 4e5, p                       # mnist-cnn's real width
    return lsh_project_sums_batched.lower(
        sds((16, p), jnp.float32), sds((), jnp.uint32), bits=BITS,
        interpret=False)


def _select(name, m):
    """fused_select / fused_select_tiled / fused_select_ann at M=m; the
    ANN kernel gets the candidate width FedConfig's defaults give."""
    def lower(sds):
        from repro.configs.paper_models import FedConfig
        from repro.core import ann
        from repro.kernels import selection
        args = [sds((m, WORDS), jnp.uint32), sds((m,), jnp.float32)]
        if name == "fused_select_ann":
            fed = FedConfig()
            k = ann.candidate_count(m, fed.ann_prefix_bits, fed.ann_probes,
                                    NEIGHBORS, BITS)
            args.append(sds((m, k), jnp.int32))
        return getattr(selection, name).lower(
            *args, bits=BITS, gamma=1.0, num_neighbors=NEIGHBORS,
            interpret=False)
    return lower


def _exchange(name, m, n, r, c):
    def lower(sds):
        from repro.kernels import exchange
        return getattr(exchange, name).lower(
            sds((m, r, c), jnp.float32), sds((m, n, r, c), jnp.float32),
            sds((m, r), jnp.int32), sds((m, n), jnp.bool_),
            interpret=False)
    return lower


CASES = {
    "lsh_batched-mnist_cnn-m16": _lsh,
    "select-m16": _select("fused_select", 16),
    "select-m4096": _select("fused_select", 4096),
    "select_tiled-m16384": _select("fused_select_tiled", 16384),
    "select_ann-m16384": _select("fused_select_ann", 16384),
    "exchange-m16-n9-r64-c10": _exchange("fused_exchange", 16, 9, 64, 10),
    "exchange_streamed-m8-n8-r64-c8192":
        _exchange("fused_exchange_streamed", 8, 8, 64, 8192),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_compiles_for_v5e(case, sds):
    compiled = CASES[case](sds).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, case
    if case.startswith("exchange_streamed"):
        # the stats kernel and the target kernel
        assert text.count("tpu_custom_call") >= 2, case


# the kernels' custom calls are named for their entry points: the
# per-layer metrics of a profile find them by these prefixes
KERNEL_NAMES = {"lsh_batched-mnist_cnn-m16": "lsh_project_sums_batched",
                "exchange-m16-n9-r64-c10": "fused_exchange",
                "select-m16": "fused_select"}
CUSTOM_CALL = re.compile(
    r"^\s*(?:ROOT )?%([\w.-]+) = .* custom-call\(.*"
    r'custom_call_target="tpu_custom_call"', re.M)


@pytest.mark.parametrize("case", list(KERNEL_NAMES))
def test_kernel_instruction_names(case, sds):
    names = CUSTOM_CALL.findall(CASES[case](sds).compile().as_text())
    assert names and all(n.startswith(KERNEL_NAMES[case]) for n in names), \
        names


def test_pallas_name_sets_the_instruction_name(sds):
    """`name=` of a pallas_call, not the jitted function around it, is
    what the compiled custom call is named after."""
    from jax.experimental import pallas as pl

    def double(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2

    @jax.jit
    def wrapper(x):
        return pl.pallas_call(double, name="kernel_name",
                              out_shape=jax.ShapeDtypeStruct(x.shape,
                                                             x.dtype))(x)

    text = wrapper.lower(sds((8, 128), jnp.float32)).compile().as_text()
    names = CUSTOM_CALL.findall(text)
    assert len(names) == 1 and names[0].startswith("kernel_name"), names


# (model, M, train rows, reference rows) of the benchmark's TCN
# configuration; 5 local steps of 64 rows
UPDATE_CASES = {"aecg_tcn-m1024": ("aecg_tcn", 1024, 201, 48),
                "aecg_tcn-m10": ("aecg_tcn", 10, 201, 48)}


@pytest.mark.parametrize("case", list(UPDATE_CASES))
def test_chunked_update_compiles_for_v5e(case, sds):
    import functools
    from repro.configs import paper_models
    from repro.core import protocol
    from repro.models import apply_client_model, init_client_model
    from repro.optim import adam
    kind, m, rows, ref_rows = UPDATE_CASES[case]
    mcfg = getattr(paper_models, kind)()
    fed = paper_models.FedConfig(num_clients=m, local_steps=5,
                                 local_batch=64)
    opt = adam(fed.lr)
    one = jax.eval_shape(functools.partial(init_client_model, mcfg),
                         jax.random.PRNGKey(0))
    width = protocol.update_chunk(
        "tpu", m, [x.shape for x in jax.tree.leaves(one)])
    assert width > 1, case
    stacked = lambda t: jax.tree.map(
        lambda x: sds((m,) + x.shape, x.dtype), t)
    x = mcfg.input_shape
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.PRNGKey(0), m))
    compiled = jax.jit(functools.partial(
        protocol._chunked_local_update,
        functools.partial(apply_client_model, mcfg), opt, fed,
        width=width)).lower(
        stacked(one), stacked(jax.eval_shape(opt.init, one)),
        {"x_train": sds((m, rows) + x, jnp.float32),
         "y_train": sds((m, rows), jnp.int32),
         "x_ref": sds((m, ref_rows) + x, jnp.float32),
         "y_ref": sds((m, ref_rows), jnp.int32)},
        sds((m, ref_rows, mcfg.num_classes), jnp.float32),
        sds((m,), jnp.bool_), sds(keys.shape, keys.dtype)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9, case


# (model, M, N, reference rows) of the benchmark's personal CNN cells
EXCHANGE_CASES = {"mnist_cnn-m128": ("mnist_cnn", 128, 12, 64),
                  "mnist_cnn-m10": ("mnist_cnn", 10, 9, 64)}


@pytest.mark.parametrize("case", list(EXCHANGE_CASES))
def test_mapped_exchange_compiles_for_v5e(case, sds):
    import functools
    from repro.configs import paper_models
    from repro.core import protocol
    from repro.models import apply_client_model, init_client_model
    kind, m, n, ref_rows = EXCHANGE_CASES[case]
    mcfg = getattr(paper_models, kind)()
    one = jax.eval_shape(functools.partial(init_client_model, mcfg),
                         jax.random.PRNGKey(0))
    width = protocol.lane_chunk(
        "tpu", m * (n + 1), [x.shape for x in jax.tree.leaves(one)])
    assert width > 1, case
    compiled = jax.jit(functools.partial(
        protocol._mapped_ref_logits,
        functools.partial(apply_client_model, mcfg), width=width)).lower(
        jax.tree.map(lambda x: sds((m,) + x.shape, x.dtype), one),
        sds((m, ref_rows) + mcfg.input_shape, jnp.float32),
        sds((m, n), jnp.int32)).compile()
    # no pair's parameters are gathered up front: the double vmap over
    # gathered parameters took 4.9 GB of scratch at m128
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9, case
