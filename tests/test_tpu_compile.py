"""Compile rehearsals for a TPU v5e at paper width, without a chip.

The TPU compiler is installed with jaxlib and compiles for a topology that is
described but not attached. Interpret mode cannot see what the TPU lowering
refuses (block shapes against the (8, 128) tiling, VMEM use, programs that do
not fit 16 GB of HBM), so these tests compile the kernels and steps of the
main path at the paper's LoGTST geometry (look-back 128, d_model 128, 16
heads of head_dim 8, 273,284 parameters) and assert the kernel made it into
the program as a ``tpu_custom_call``.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import dataclasses
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from repro.common.pytree_utils import tree_flatten_to_vector
from repro.core import forecast
from repro.core.fl import engine as E
from repro.core.fl import policies as pol
from repro.core.tasks import get_task, task_forecaster

V5E_HBM_BYTES = 16 * 1024 ** 3
PAPER_PARAMS = 273_284


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs under /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The kernels resolve ``interpret=None`` from the default backend, which
    is the CPU here; steer the wrappers to the compiled kernel, as they
    resolve on the chip."""
    from repro.kernels.flash_attention import ops as flash_ops
    from repro.kernels.psgf_mix import ops as mix_ops

    for mod in (flash_ops, mix_ops):
        monkeypatch.setattr(mod, "resolve_interpret", lambda interpret: False)


@pytest.fixture(scope="module")
def paper_cfg():
    task = get_task("ev", quick=False)
    cfg = task_forecaster(task, "logtst", quick=False).cfg
    assert forecast.num_params(cfg) == PAPER_PARAMS
    return cfg


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _shapes_like(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: _shape(sharding, a.shape, a.dtype), tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_psgf_mix_compiles(one_chip):
    from repro.kernels.psgf_mix.ops import psgf_mix

    D = PAPER_PARAMS
    c = _compile(lambda g, l, m: psgf_mix(g, l, m, interpret=False),
                 _shape(one_chip, (D,)), _shape(one_chip, (D,)),
                 _shape(one_chip, (D,), jnp.bool_))
    assert "tpu_custom_call" in c.as_text()


def test_psgf_mix_batch_compiles(one_chip):
    """K = 33, the larger cluster of the EV full preset at clusters=2."""
    from repro.kernels.psgf_mix.ops import psgf_mix_batch

    K, D = 33, PAPER_PARAMS
    c = _compile(lambda g, w, m: psgf_mix_batch(g, w, m, interpret=False),
                 _shape(one_chip, (D,)), _shape(one_chip, (K, D)),
                 _shape(one_chip, (K, D)))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("tokens", [15, 63],
                         ids=["logtst_lb128", "patchtst_lb512"])
def test_flash_attention_compiles(one_chip, tokens):
    """Forecaster geometry: 16 heads of head_dim 8, bidirectional."""
    from repro.kernels.flash_attention.ops import flash_attention

    qkv = _shape(one_chip, (32, tokens, 16, 8))
    c = _compile(lambda q, k, v: flash_attention(q, k, v, causal=False,
                                                 interpret=False),
                 qkv, qkv, qkv)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("flash", [False, True], ids=["dense", "flash"])
def test_serving_step_compiles(one_chip, paper_cfg, compiled_kernels, flash):
    """The server's bucketed donated-output step at bucket 32, 3 channels."""
    from repro.launch.serve_forecast import _bucket_step

    cfg = dataclasses.replace(paper_cfg, use_flash_attn=flash)
    params = jax.eval_shape(lambda k: forecast.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    bucket, M = 32, 3
    step = _bucket_step.__wrapped__(cfg)   # a fresh jit, not the cached one
    c = step.lower(_shapes_like(params, one_chip),
                   _shape(one_chip, (bucket, M, cfg.look_back)),
                   _shape(one_chip, (bucket, M, cfg.horizon))).compile()
    assert ("tpu_custom_call" in c.as_text()) == flash


def test_round_body_compiles(one_chip, paper_cfg, compiled_kernels):
    """One FL round at paper width on a 33-client cluster with the fused
    downlink kernel and streaming windows; it must fit one chip's HBM."""
    K = 33
    fl_cfg = E.FLConfig(policy="psgf", num_clients=K, streaming_windows=True,
                        use_pallas_mix=True)
    key = jax.random.PRNGKey(0)
    _, meta = tree_flatten_to_vector(forecast.init_params(paper_cfg, key))
    state = jax.eval_shape(
        lambda k: E.init_fl_state(paper_cfg, fl_cfg, k)[0], key)
    round_fn = jax.jit(E._round_body, static_argnums=(3, 4, 5, 6))
    c = round_fn.lower(_shapes_like(state, one_chip),
                       _shape(one_chip, (K, 2000)),
                       _shape(one_chip, key.shape, key.dtype),
                       paper_cfg, fl_cfg, meta,
                       pol.from_config(fl_cfg)).compile()
    assert "tpu_custom_call" in c.as_text()
    mem = c.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, used


def test_window_fetch_compiles_to_a_row_gather(one_chip):
    """The streaming local update at the paper's EV data shape (58 clients,
    a 332-step train slice, look-back 128 + horizon 2, batch 32, four steps)
    takes its windows in one gather of 130-wide rows: neither a gather of
    single elements nor a loop over the windows, the two forms that cost
    ~10 ms a round on the chip. Narrow model widths keep the compile short;
    the fetch does not depend on them."""
    cfg = forecast.logtst_config(look_back=128, horizon=2, d_model=8,
                                 num_heads=2, d_ff=16, patch_len=16, stride=8)
    K, T, W = 58, 332, 130
    fl_cfg = E.FLConfig(policy="psgf", num_clients=K, streaming_windows=True,
                        local_steps=4, batch_size=32)
    state, meta = E.init_fl_state(cfg, fl_cfg, jax.random.PRNGKey(0))
    client = [state[k] for k in ("w_clients", "adam_m", "adam_v", "adam_t")]
    c = jax.jit(partial(E._local_update_all, cfg, fl_cfg, meta)).lower(
        *_shapes_like(client, one_chip), _shape(one_chip, (K, T)),
        _shape(one_chip, (K, 2), jnp.uint32)).compile()
    fetch = [line for line in c.as_text().splitlines()
             if "fl.window_gather" in line
             and (" while(" in line or " gather(" in line)]
    assert fetch
    for line in fetch:
        assert " while(" not in line, line
        assert re.search(r"slice_sizes=\{[\d,]*,%d\}" % W, line), line
