# Pallas TPU kernels for the compute hot-spots the paper optimizes:
#   edge_scan     — the Scanner's candidate-edge accumulation (§4.1)
#   weight_update — fused incremental strong-rule re-weighting (§4.1)
#   round_step / queue_ingest — the engine's sparse delivery (round_step.py)
# Each has a pure-jnp oracle in ref.py and a jit'd wrapper in ops.py.
# They compile to Mosaic on a TPU and run in interpret mode elsewhere
# (platform.py).

from repro.kernels import ops
from repro.kernels.weight_update import scatter_model_slice

__all__ = ["ops", "scatter_model_slice"]
