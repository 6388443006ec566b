"""Compile the simulator's device programs for a described TPU v5e.

Nothing runs: each test lowers and compiles for one chip of a described
``v5e:2x2`` topology, so the chip's own compiler accepts or refuses the
program here, at no chip time.  The topology is described in a fixture,
never at import: one process at a time may load the TPU library, and
every test worker imports this file.  The persistent compilation cache
is off around these compiles — an entry written for a chip that is not
attached cannot be read back.
"""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

#: the simulator's L2 (8 requesters × 512 sets × 8 ways) and shared L3
#: (8192 sets × 16 ways) set geometry, as (sets, ways)
L2_SETS, L3_SETS = (8 * 512, 8), (8192, 16)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                       sharding=sharding), tree)


def test_engine_baseline_bucket_compiles(one_chip):
    """The batched scan of the ``baseline`` bucket (L1/L2, MESI, DRAM)
    and its counter export compile for the chip.  A short trace slice
    and two lanes still build the whole step body."""
    from repro.core import engine_jax as E
    from repro.core import trace as trace_mod
    from repro.core.presets import BASELINE

    tr = dict(trace_mod.WORKLOADS["cnn"](scale=0.001))
    for k in ("core", "pc", "addr", "write", "tensor", "reuse"):
        tr[k] = tr[k][:256]
    lanes = 2
    with jax.enable_x64(True):
        caps = E.Caps.of(tr)
        static, cfg = E.split_config(BASELINE, caps.nten)
        prep = E.prepare_trace(static, tr, caps)
        cfgs = E._cfg_stack([cfg] * lanes)
        st = {k: np.broadcast_to(v, (lanes,) + np.shape(v))
              for k, v in E.init_state(static, prep).items()}
        xs = {k: v[:, None] for k, v in prep.xs.items()}
        args = _shapes(({"blk": prep.blk_tab[None]}, cfgs, st, xs,
                        np.zeros(lanes, np.int32)), one_chip)
        scan, export = E._make_run(static, batched=True)
        compiled = scan.lower(*args).compile()
        export.lower(args[2]).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 30, mem


@pytest.mark.parametrize("sets,ways", [L2_SETS, L3_SETS],
                         ids=["l2", "l3"])
def test_tag_probe_kernel_compiles(one_chip, sets, ways):
    """The Pallas tag-probe kernel compiles with Mosaic (not interpret
    mode) at the simulator's set geometry."""
    from repro.kernels.tag_probe import tag_probe

    i32 = jnp.int32
    args = [jax.ShapeDtypeStruct((sets, ways), dt, sharding=one_chip)
            for dt in (i32, i32, jnp.float32, i32)]
    args.append(jax.ShapeDtypeStruct((sets,), i32, sharding=one_chip))
    compiled = tag_probe.lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
