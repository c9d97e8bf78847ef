"""Driver of ``repro.kernels.stencil_apply``: the engine's own choice of
path, plan and blocks, with only the deployment's operator, BC and sweeps."""

import repro.kernels as K


def make(config: dict, traffic: dict, mesh):
    op, bc, sweeps = config["operator"], config["bc"], traffic["sweeps"]

    def call(u, w):
        return K.stencil_apply(u, w, op, sweeps=sweeps, bc=bc)
    return call
