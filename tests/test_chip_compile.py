"""Compile the Pallas kernels for a TPU v5e chip, without the chip.

Each test lowers a kernel at the widths the main path runs and compiles
it with the installed TPU compiler against a described ``v5e:2x2``
topology; the compiled program must contain the Mosaic kernel
(``tpu_custom_call``). This catches what interpret mode cannot: shape
casts Mosaic does not support, scalar stores to VMEM, and kernels that
overrun the scoped VMEM. Nothing runs, so results are checked by
``tests/test_kernels.py`` and on the chip by ``chip_smoke.py``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and under pytest-xdist
every worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep these out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compiled_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("d,num_bins", [(64, 8), (128, 32)])
def test_edge_scan(one_chip, d, num_bins):
    c = 2048
    text = _compiled_text(
        lambda x, wy, w: ops.edge_scan(x, wy, w, num_bins=num_bins, tile_n=512, interpret=False),
        one_chip,
        ((c, d), jnp.int32),
        ((c,), jnp.float32),
        ((c,), jnp.float32),
    )
    assert "tpu_custom_call" in text


def test_edge_scan_batched(one_chip):
    """The engine's scan path: the kernel vmapped over 16 workers."""
    w, c, d = 16, 2048, 64
    text = _compiled_text(
        lambda x, wy, wt: ops.edge_scan_batched(x, wy, wt, num_bins=8, tile_n=512, interpret=False),
        one_chip,
        ((w, c, d), jnp.int32),
        ((w, c), jnp.float32),
        ((w, c), jnp.float32),
    )
    assert "tpu_custom_call" in text


def test_weight_update(one_chip):
    n, d, num_bins = 2048, 64, 8
    text = _compiled_text(
        lambda x, y, ml, ms, a, c: ops.weight_update(
            x, y, ml, ms, a, c, num_bins=num_bins, interpret=False
        ),
        one_chip,
        ((n, d), jnp.int32),
        ((n,), jnp.float32),
        ((n,), jnp.float32),
        ((n,), jnp.float32),
        ((d, num_bins - 1), jnp.float32),
        ((), jnp.float32),
    )
    assert "tpu_custom_call" in text


W, CAP = 4096, 64


def test_round_step(one_chip):
    text = _compiled_text(
        lambda qc, qd, qs, ql, c0, al, cr, sp, r: ops.round_deliver(
            qc, qd, qs, ql, c0, al, cr, sp, r, eps=0.0, interpret=False
        ),
        one_chip,
        ((W, CAP), jnp.float32),
        ((W, CAP), jnp.int32),
        ((W, CAP), jnp.int32),
        ((W, CAP), jnp.int32),
        ((W,), jnp.float32),
        ((W,), jnp.bool_),
        ((W,), jnp.float32),
        ((W,), jnp.float32),
        ((), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_queue_ingest(one_chip):
    m = 8  # candidate block width of the sparse control plane
    text = _compiled_text(
        lambda *a: ops.queue_ingest(*a, interpret=False),
        one_chip,
        ((W, CAP), jnp.float32),
        ((W, CAP), jnp.int32),
        ((W, CAP), jnp.int32),
        ((W, CAP), jnp.int32),
        ((W, m), jnp.float32),
        ((W, m), jnp.int32),
        ((W, m), jnp.int32),
        ((W, m), jnp.int32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("target", [None, -0.45], ids=["rounds", "to-target"])
def test_engine_chunk_never_copies_the_sample_bins(one_chip, monkeypatch, target):
    """The Sparrow engine's chunk program at the benchmark's widths (16
    workers, 141 features, 2048-example scan chunks; a smaller sample and
    disk set): no computation but the entry copies the (W, m, d) sample
    bins, and they keep one layout. The entry copies the chunk's argument
    once, as it is not donated. A per-round cond that carries the bins,
    or phases that want them in different layouts, put such copies inside
    the round step, where they run every round."""
    from repro.boosting import BatchedSparrowWorker, SparrowConfig
    from repro.boosting.scanner import ScannerConfig
    from repro.core.engine import EngineConfig, TMSNEngine
    from repro.core.worker import bind_shared_data
    from repro.kernels import edge_scan, round_step, weight_update
    from repro.launch.hlo_analysis import parse_instruction, split_computations

    for mod in (edge_scan, round_step, weight_update):
        monkeypatch.setattr(mod, "resolve_interpret", lambda interpret: False)
    w, m, d, n = 16, 4096, 141, 32768
    kx, ky = jax.random.split(jax.random.PRNGKey(0))
    xb = jax.random.randint(kx, (n, d), 0, 4, jnp.int32)
    y = jnp.where(jax.random.bernoulli(ky, 0.01, (n,)), 1.0, -1.0)
    cfg = SparrowConfig(
        sample_size=m,
        capacity=256,
        scanner=ScannerConfig(chunk_size=2048, num_bins=4, use_kernel=True),
        n_workers=w,
    )
    worker = BatchedSparrowWorker(xb, y, cfg)
    eng = TMSNEngine(
        worker,
        EngineConfig(n_workers=w, max_rounds=16, seed=1, rounds_per_dispatch=8, fault_spec="",
                     inflight_capacity=64, target_certificate=target,
                     record_history=target is not None),
    )
    put = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    state = jax.tree_util.tree_map(put, jax.eval_shape(eng._init_state))
    shared = (put(xb), put(y))

    def chunk(state, shared):
        with bind_shared_data(worker, shared):
            return eng._chunk_rounds(eng._round_step, jnp.any, state, 8)

    text = jax.jit(chunk).lower(state, shared).compile().as_text()
    bins = f"s32[{w},{m},{d}]"
    comps, entry = split_computations(text)
    copies, layouts = {}, set()
    for name, lines in comps.items():
        for line in lines:
            ins = parse_instruction(line)
            if ins is not None and ins.type_text.startswith(bins):
                layouts.add(ins.type_text.split(":")[0])
                if ins.opcode in ("copy", "copy-start"):
                    copies[name] = copies.get(name, 0) + 1
    assert copies == {entry: 1}
    assert len(layouts) == 1, layouts
