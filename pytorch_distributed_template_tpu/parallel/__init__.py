from .dist import (
    initialize,
    process_index,
    process_count,
    is_main_process,
    synchronize,
    all_gather_object,
    local_device_count,
    global_device_count,
)
from .mesh import build_mesh, mesh_from_config, MESH_AXES
from .sharding import (
    batch_sharding,
    replicated_sharding,
    named_sharding,
    make_state_sharding,
    apply_rules,
    train_step_compile_options,
)
from .tp import (
    TP_AXIS,
    serving_mesh,
    tp_degree,
    validate_tp_geometry,
    model_geometry,
    kv_pool_pspec,
    shard_kv_tree,
    constrain_kv_tree,
    shard_serving_params,
    decode_step_collectives,
    analytic_decode_floor_bytes,
    hlo_collectives,
)
