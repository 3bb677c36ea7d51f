# Pallas TPU kernels for the paper's compute hot spots:
#   bincount.py          — global result reduction (replaces §IV-C atomic
#                          hash tables)
#   propagate.py         — ELL row sums with blocked weight streaming
#                          (replaces §IV-B per-thread rule walk)
#   propagate_batched.py — fused batched ELL propagation round (delta+seen
#                          in one launch over the [N, R, K] edge plan)
# ops.py: jit'd wrappers + ELL-vs-segment_sum dispatch and the per-platform
# execution path (only bincount lowers for TPU); ref.py: pure-jnp oracles
# (and the production path of the ELL plans on TPU and CPU).
from . import ops, ref  # noqa: F401
