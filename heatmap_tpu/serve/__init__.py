"""heatmap_tpu.serve — the read side of the system: tile serving.

The reference job existed to FEED a serving path: blobs went into
Cassandra so a map frontend could fetch heatmap tiles (reference
heatmap.py:149-150); the query path itself lived in some other service.
This package is that service, TPU-framework-native:

- ``store``  — TileStore: batch egress (``arrays:DIR`` per-level npz,
  including multihost ``host*/`` shards, or ``jsonl:``/``dir:`` blob
  records) loaded into a read-optimized Morton-keyed per-zoom index
  with named layers and hot ``reload()``;
- ``cache``  — TileCache: thread-safe byte-capped LRU with TTL,
  single-flight render dedup and generation invalidation;
- ``render`` — on-demand tile materialization: exact tiles at stored
  zooms, 2x2 rollup / quadrant upsample at zooms the pyramid lacks,
  PNG (io/png colormap) or reference-compatible JSON counts;
- ``live``   — a HeatmapStream-backed layer whose update ticks
  invalidate only the affected tile keys;
- ``http``   — stdlib ThreadingHTTPServer frontend with ETag/304,
  ``/healthz`` and a Prometheus ``/metrics`` endpoint (obs registry);
- ``router`` — stateless fleet frontend: rendezvous hashing with
  bounded-load spill, circuit breakers, hedged reads, admission
  control (typed 503 + Retry-After, never a 500);
- ``fleet``  — supervisor spawning N shared-nothing backend processes
  behind one router, restarting crashers with backoff and re-admitting
  them via half-open health probes.

Everything except ``live`` is numpy-only — serving a finished job
never initializes a jax backend (the io/merge.py offline property), so
a tile server needs no device.
"""

from heatmap_tpu.serve.cache import TileCache  # noqa: F401
from heatmap_tpu.serve.store import TileStore  # noqa: F401
from heatmap_tpu.serve.render import (  # noqa: F401
    tile_array,
    tile_json_bytes,
    tile_png_bytes,
)
from heatmap_tpu.serve.http import (  # noqa: F401
    ServeApp,
    make_server,
    serve_in_thread,
)
from heatmap_tpu.serve.live import LiveLayer  # noqa: F401
from heatmap_tpu.serve.router import (  # noqa: F401
    BackendClient,
    CircuitBreaker,
    RouterApp,
    rendezvous_order,
    route_key,
)
from heatmap_tpu.serve.fleet import FleetSupervisor  # noqa: F401
