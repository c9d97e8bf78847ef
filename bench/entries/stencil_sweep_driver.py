"""Driver of ``repro.kernels.stencil_sweep_driver`` in its ``auto`` mode:
the engine races fused, wavefront and chained sweeps itself."""

import repro.kernels as K


def make(config: dict, traffic: dict, mesh):
    op, bc, sweeps = config["operator"], config["bc"], traffic["sweeps"]

    def call(u, w):
        return K.stencil_sweep_driver(u, w, op, sweeps=sweeps, bc=bc)
    return call


def describe(config: dict, traffic: dict) -> str:
    """The mode the engine's public autotuner picks for this shape."""
    m, n, p = config["per_chip"]
    itemsize = 2 if config["dtype"] == "bfloat16" else 4
    sel = K.autotune_sweeps(m, n, p, itemsize, traffic["sweeps"],
                            K.compile_plan(config["operator"]))
    return (f"auto_mode={sel.mode} path={sel.path} "
            f"blocks=({sel.block_i}, {sel.block_j})")
