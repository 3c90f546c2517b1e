"""Distribution subsystem: mesh context, sharding planner, collectives."""
from repro.dist import collectives, context, sharding  # noqa: F401
from repro.dist.context import (axis_size, constrain, constrain_dims,  # noqa: F401
                                dp_axes, get_mesh, mesh_context,
                                set_batch_axes)
from repro.dist.sharding import (cache_shardings, input_shardings,  # noqa: F401
                                 param_shardings, param_specs_tree,
                                 pick_strategy, sanitize_spec)
from repro.dist.collectives import (compress_psum, seq_sharded_decode,  # noqa: F401
                                    seq_sharded_write_decode,
                                    set_fused_partials)
