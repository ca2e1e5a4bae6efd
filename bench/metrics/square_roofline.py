"""Share of the chip's roofline that the squaring kernels reached.

Work: the squarings the reference algorithm needs for the answers
completed in the traced window (Pade-13 scaling and squaring from each
operand's 1-norm; for a steady state, the squarings the program reports),
2 n^3 operations and 3 n^2 * 4 bytes each at the unpadded n. Time: the
device time of the kernels named below, which the chains and the
Strassen leaves run today: on a TPU v5e trace their ops read
``%matmul_pallas.N`` (custom call ``tpu_custom_call``). A kernel renamed
or replaced leaves the metric silent until the benchmark names it again.
"""

import sys

from mfbench import roofline

KERNELS = ("matmul_pallas", "square_pallas")


def read(r):
    if r.device is None:
        return None
    share = roofline.roofline_share(r.squarings, r.n,
                                    r.device.kernel_seconds(KERNELS),
                                    r.device_kind)
    if share is None:
        return None
    print(f"[bench] square_roofline bound by {share[1]}", file=sys.stderr)
    return share[0]
