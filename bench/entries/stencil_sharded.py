"""Driver of ``repro.kernels.stencil_sharded`` on the configuration's mesh,
with the engine's own shard plan, mode and blocks."""

import repro.kernels as K


def make(config: dict, traffic: dict, mesh):
    op, bc, sweeps = config["operator"], config["bc"], traffic["sweeps"]
    axis = mesh.axis_names[0]

    def call(u, w):
        return K.stencil_sharded(u, w, op, mesh=mesh, axis=axis,
                                 sweeps=sweeps, bc=bc)
    return call
