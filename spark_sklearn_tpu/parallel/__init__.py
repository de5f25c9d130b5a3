from spark_sklearn_tpu.parallel.mesh import TpuConfig, build_mesh, replicate, shard_leading
from spark_sklearn_tpu.parallel.pipeline import (
    ChunkPipeline, LaunchItem, enable_persistent_cache,
    persistent_cache_counts)
from spark_sklearn_tpu.parallel.taskgrid import (
    CompileGroup, GeometryCostModel, GeometryMismatchError, GeometryPlan,
    build_compile_groups, build_fold_masks, geometry_cost_model,
    plan_geometry)
from spark_sklearn_tpu.parallel.dataplane import (
    DataPlane, get_dataplane)
