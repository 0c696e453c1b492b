"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod: (pod=2, data=16, model=16) = 512 chips.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — the dry-run must set
``xla_force_host_platform_device_count`` before the first jax call.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with Auto axes: the engines and steps partition
    through ``shard_map`` and sharding annotations, not through
    sharding-in-types (which ``make_mesh`` defaults to on JAX >= 0.8)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke runs of the same step functions."""
    return _mesh((1, 1), ("data", "model"))


def make_worker_mesh(num_devices: int | None = None, pods: int = 1):
    """Worker mesh for the device-sharded TMSN engine.

    ``pods=1`` (default) builds the 1-D ``("workers",)`` mesh: one
    interconnect tier, gossip is a single all_gather over every device.
    ``pods > 1`` builds the hierarchical 2-D ``("pod", "workers")``
    mesh — ``pods`` groups of ``num_devices / pods`` devices each, with
    ``pod`` as the slow (device-order-major) axis so the flat device
    order matches the 1-D mesh. The engine then keeps per-round gossip
    on the ``workers`` (ICI) axis and exchanges only the freshest
    pending certificates over the ``pod`` (DCN) axis every
    ``EngineConfig.cross_pod_every_k`` rounds.

    ``num_devices=None`` takes every visible device (on CI that is the
    8 forced host devices from ``--xla_force_host_platform_device_count``;
    on a TPU pod slice, the real chips). The engine shards the stacked
    ``(W, ...)`` worker state over the whole mesh, so ``n_workers`` must
    be a multiple of the total device count.
    """
    if num_devices is None:
        num_devices = len(jax.devices())
    if num_devices < 1 or num_devices > len(jax.devices()):
        raise ValueError(
            f"num_devices={num_devices} not in [1, {len(jax.devices())}] visible devices"
        )
    if pods < 1:
        raise ValueError(f"pods={pods} must be >= 1")
    if pods == 1:
        return _mesh((num_devices,), ("workers",))
    if num_devices % pods:
        raise ValueError(f"num_devices={num_devices} must divide into {pods} pods")
    return _mesh((pods, num_devices // pods), ("pod", "workers"))


def data_axes(mesh) -> tuple[str, ...]:
    """Axes the global batch is sharded over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


# Hardware constants for the roofline (TPU v5e per chip)
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link
# data-center network between pods — order 100 Gbit/s per host, an
# order of magnitude under ICI; the gap is why the pod-mesh engine
# moves cross-pod payloads only every cross_pod_every_k rounds
DCN_BW = 12.5e9  # B/s


def ici_round_seconds(
    gossip_bytes_per_round: int,
    bandwidth: float = ICI_BW,
    control_bytes_per_round: int = 0,
) -> float:
    """Lower-bound wire seconds one gossip round would spend on a single
    ICI link, from the engine's logical ``gossip_bytes_per_round``.

    ``control_bytes_per_round`` adds the control-plane exchange
    (certificates/flags/ids) when the caller reports the two planes
    separately — pass 0 (default) when the gossip figure already
    includes it, as ``SimResult.gossip_bytes_per_round`` does.

    A derived estimate for benchmark reporting (dense vs gated gossip,
    dense vs sparse control), not a measurement — the ROADMAP's
    real-interconnect item is about replacing this with profiler traces
    on hardware."""
    return float(gossip_bytes_per_round + control_bytes_per_round) / float(bandwidth)


def dcn_round_seconds(
    dcn_bytes_per_round: int,
    bandwidth: float = DCN_BW,
    control_bytes_per_round: int = 0,
) -> float:
    """Lower-bound wire seconds per round on the cross-pod DCN tier,
    from the pod-mesh engine's amortized ``gossip_bytes_per_round_dcn``
    (plus, optionally, a separately-reported control-plane share).
    Same derived-not-measured formula as the ICI tier, at DCN bandwidth."""
    return ici_round_seconds(
        dcn_bytes_per_round, bandwidth, control_bytes_per_round=control_bytes_per_round
    )
