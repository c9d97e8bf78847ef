from .planner import (PlanNote, StencilGridPlan,  # noqa: F401
                      batch_sharding, decode_state_sharding, param_sharding,
                      plan_summary, stencil_grid_sharding)
