"""Compile the simulator's device programs for a described TPU v5e.

Nothing runs: each test lowers and compiles for one chip of a described
``v5e:2x2`` topology, so the chip's own compiler accepts or refuses the
program here, at no chip time.  The topology is described in a fixture,
never at import: one process at a time may load the TPU library, and
every test worker imports this file.  The persistent compilation cache
is off around these compiles — an entry written for a chip that is not
attached cannot be read back.
"""

import dataclasses
import math
import os
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

#: the simulator's L2 (8 requesters × 512 sets × 8 ways) and shared L3
#: (8192 sets × 16 ways) set geometry, as (sets, ways)
L2_SETS, L3_SETS = (8 * 512, 8), (8192, 16)

#: lanes and markov-table slots of the tensor-aware bucket: at these the
#: compiler keeps the carry's slot axis minor, as at the paper-size caps
#: (65,536 slots), and a table read a row at a time would be relaid out
TA_LANES, TA_MK = 4, 16384


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


def _bucket_args(sp, lanes, sharding, mk=None):
    """``(scan, export, args)`` of the batched bucket of ``sp`` on a
    256-access slice of cnn, its markov table at ``mk`` slots if given:
    a short slice and few lanes still build the whole step body."""
    from repro.core import engine_jax as E
    from repro.core import trace as trace_mod

    tr = dict(trace_mod.WORKLOADS["cnn"](scale=0.001))
    for k in ("core", "pc", "addr", "write", "tensor", "reuse"):
        tr[k] = tr[k][:256]
    caps = E.Caps.of(tr)
    if mk is not None:
        caps = dataclasses.replace(caps, mk=mk)
    static, cfg = E.split_config(sp, caps.nten)
    prep = E.prepare_trace(static, tr, caps)
    cfgs = E._cfg_stack([cfg] * lanes)
    st = {k: np.broadcast_to(v, (lanes,) + np.shape(v))
          for k, v in E.init_state(static, prep).items()}
    xs = {k: v[:, None] for k, v in prep.xs.items()}
    args = _shapes(({"blk": prep.blk_tab[None]}, cfgs, st, xs,
                    np.zeros(lanes, np.int32)), sharding)
    scan, export = E._make_run(static, batched=True)
    return scan, export, args


def _loop_copies(hlo: str, n: int):
    """The ``copy``/``copy-start`` instructions with an array of ``n``
    elements in the while bodies of optimised HLO text, or in the
    computations they call."""
    comps, name = {}, None
    for line in hlo.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            name = re.match(r"(?:ENTRY )?%?([\w.\-]+)", line).group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name:
            comps[name].append(line)
    todo = set(re.findall(r"body=%?([\w.\-]+)", hlo))
    seen = set()
    while todo:
        c = todo.pop()
        seen.add(c)
        for line in comps.get(c, []):
            todo |= set(re.findall(
                r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)",
                line)) - seen
    out = []
    for c in seen:
        for line in comps.get(c, []):
            m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) (copy|copy-start)\(",
                         line)
            if m and n in (math.prod(map(int, filter(None, d.split(","))))
                           for d in re.findall(r"\w\d+\[([\d,]*)\]",
                                               m.group(1))):
                out.append(line.strip()[:160])
    return out


def test_engine_baseline_bucket_compiles(one_chip):
    """The batched scan of the ``baseline`` bucket (L1/L2, MESI, DRAM)
    and its counter export compile for the chip."""
    from repro.core.presets import BASELINE

    with jax.enable_x64(True):
        scan, export, args = _bucket_args(BASELINE, 2, one_chip)
        compiled = scan.lower(*args).compile()
        export.lower(args[2]).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 30, mem


def test_engine_tensor_aware_bucket_copies_no_markov_table(one_chip):
    """The batched ``tensor_aware`` scan compiles for the chip, and no
    copy in its loops moves a whole markov table (``mkd``, ``mko``): a
    row is read and written by point gathers and scatters in the
    carry's own layout.  Rows held minor (``[R, mk, 9]``) cost four
    relayouts of the tables per step, most of a 32-lane step."""
    from repro.core.presets import TENSOR_AWARE

    with jax.enable_x64(True):
        scan, _, args = _bucket_args(TENSOR_AWARE, TA_LANES, one_chip,
                                     mk=TA_MK)
        hlo = scan.lower(*args).compile().as_text()
    assert _loop_copies(hlo, math.prod(args[2]["mkd"].shape)) == []


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
