"""Device time of expm's LU solve per answer in the tandem transient
cell: the own time of the ops whose names hold one of the custom-call
targets below (a TPU v5e trace names them so; its op events carry no
op_name metadata), over the answers completed in the traced window."""

SOLVE_OPS = ("LuDecomposition", "InvertDiagBlocks", "TriangularSolve")


def read(r):
    if r.device is None or not r.answers:
        return None
    seconds = r.device.kernel_seconds(SOLVE_OPS)
    if seconds <= 0:
        return None
    return 1e3 * seconds / r.answers
