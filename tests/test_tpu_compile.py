"""The Pallas kernels compile for a TPU v5e chip at real widths.

Nothing runs: each test lowers and compiles for a *described* v5e chip
(the TPU compiler is installed without a chip attached) and checks that
the kernel is in the program (``tpu_custom_call``).  Interpret mode, which
every other kernel test uses, cannot see what the chip's compiler refuses:
block shapes off the (8, 128) tile, scalar stores to vector memory, shape
casts its layouts do not support.

* the fused optimizer at ResNet-56 leaf shapes — a (3, 3, 64, 64) conv
  kernel and a (16,) gain — solo and member-stacked (M = 4, what ``vmap``
  over a sibling group produces);
* flash attention forward and backward at qwen2-0.5b widths;
* SSD forward and backward at mamba2-2.7b widths.

The topology is described inside a fixture (never at import: only one
process at a time may load the TPU library), and the persistent
compilation cache is off around these compiles, since an entry written
for a described chip cannot be read back without one.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import (flash_attention_bwd,
                                           flash_attention_fwd)
from repro.kernels.optim import fused_apply_update
from repro.kernels.ssd_scan import ssd_intra_bwd_pallas, ssd_intra_pallas

SEQ = 2048


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    """Steer the kernels off interpret mode: they ask the default backend,
    which is the CPU here."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("shape", [(3, 3, 64, 64), (16,)],
                         ids=["conv3x3x64x64", "gain16"])
@pytest.mark.parametrize("members", [1, 4], ids=["solo", "M4"])
@pytest.mark.parametrize("name", ["momentum", "adam"])
def test_fused_optimizer_compiles(one_chip, mosaic, name, members, shape):
    lead = (members,) if members > 1 else ()
    leaf = jax.ShapeDtypeStruct(lead + shape, jnp.float32, sharding=one_chip)
    scalar = jax.ShapeDtypeStruct(lead, jnp.float32, sharding=one_chip)
    state = {"m": leaf, "v": leaf} if name == "adam" else {"m": leaf}
    hp = {"lr": scalar, "wd": scalar}

    def update(p, g, s, h):
        return fused_apply_update(name, p, g, s, h, jnp.int32(3))

    fn = jax.vmap(update) if members > 1 else update
    assert "tpu_custom_call" in _compiled_text(fn, leaf, leaf, state, hp)


def _attention_shapes(one_chip):
    cfg = get_config("qwen2-0.5b")
    hd = cfg.resolved_head_dim
    q = jax.ShapeDtypeStruct((1, SEQ, cfg.num_heads, hd), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, SEQ, cfg.num_kv_heads, hd), jnp.bfloat16,
                              sharding=one_chip)
    lse = jax.ShapeDtypeStruct((1, cfg.num_heads, SEQ), jnp.float32,
                               sharding=one_chip)
    return q, kv, lse


def test_flash_attention_forward_compiles(one_chip):
    q, kv, _ = _attention_shapes(one_chip)
    fwd = lambda q, k, v: flash_attention_fwd(q, k, v, return_lse=True,
                                              interpret=False)
    assert "tpu_custom_call" in _compiled_text(fwd, q, kv, kv)


def test_flash_attention_backward_compiles(one_chip):
    q, kv, lse = _attention_shapes(one_chip)
    bwd = lambda q, k, v, o, l, do: flash_attention_bwd(q, k, v, o, l, do,
                                                        interpret=False)
    assert "tpu_custom_call" in _compiled_text(bwd, q, kv, kv, q, lse, q)


def _ssd_shapes(one_chip):
    cfg = get_config("mamba2-2.7b")
    Q, H, P, N = cfg.ssm_chunk, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    nc = SEQ // Q
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    x = S((1, nc, Q, H, P), jnp.bfloat16)
    return (x, S((1, nc, Q, H), jnp.float32), S((1, nc, H, Q), jnp.float32),
            S((1, nc, Q, N), jnp.bfloat16), S((1, nc, Q, N), jnp.bfloat16))


def test_ssd_forward_compiles(one_chip):
    fwd = lambda *a: ssd_intra_pallas(*a, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fwd, *_ssd_shapes(one_chip))


def test_ssd_backward_compiles(one_chip):
    args = _ssd_shapes(one_chip)
    bwd = lambda *a: ssd_intra_bwd_pallas(*a, interpret=False)
    assert "tpu_custom_call" in _compiled_text(bwd, *args, args[0])
