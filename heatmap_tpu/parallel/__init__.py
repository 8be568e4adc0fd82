"""Device-mesh parallelism: the TPU-native replacement for Spark's cluster.

The reference scales by elastic Spark executors + a netty shuffle
service (reference submit-heatmap:9-13); here the same roles are played
by a ``jax.sharding.Mesh`` and XLA collectives over ICI/DCN:

- points are sharded over the ``data`` mesh axis (the RDD-partition
  analog, reference heatmap.py:154);
- partial tile rasters merge with ``lax.psum`` (reduceByKey analog) or
  ``lax.psum_scatter`` when the merged raster should itself stay
  sharded over the ``tile`` axis (groupByKey analog);
- sparse per-key aggregates merge via ``all_gather`` + local re-reduce.

Everything works identically on a single host (8 virtual CPU devices in
tests), one real TPU chip, or a multi-host DCN-spanning mesh — only the
mesh construction differs (mesh.py).
"""

from heatmap_tpu.parallel.mesh import (  # noqa: F401
    DATA_AXIS,
    TILE_AXIS,
    force_cpu_devices,
    make_mesh,
    named_sharding,
    pad_to_multiple,
)
from heatmap_tpu.parallel.gspmd import (  # noqa: F401
    DonatedBufferError,
    DonationLedger,
    donating_jit,
    donation_supported,
    pyramid_gspmd_range,
    pyramid_gspmd_uniform,
    route_on_device,
)
from heatmap_tpu.parallel.sharded import (  # noqa: F401
    aggregate_keys_sharded,
    bin_points_bandsharded,
    bin_points_replicated,
    bin_points_rowsharded,
    pyramid_rowsharded,
    splat_rowsharded,
)
from heatmap_tpu.parallel.partition import (  # noqa: F401
    PartitionPlan,
    plan_partition,
)
from heatmap_tpu.parallel.multihost import (  # noqa: F401
    StragglerTimeout,
    check_heartbeats,
    gather_blobs,
    initialize,
    make_hybrid_mesh,
    process_shard_bounds,
    run_job_multihost,
    shard_source,
    shard_source_rows,
)
from heatmap_tpu.parallel.elastic import (  # noqa: F401
    ElasticCoordinator,
    ShardLineage,
    WorkShard,
    job_fingerprint,
    plan_shards,
    run_job_elastic,
    shard_fingerprint,
)
