"""Trajectory analytics & feature-query subsystem.

Turns the compressor's preserved critical-point trajectories into
queryable objects:

* ``extract``             -- full geometric extraction: space-time
                             polylines + CP types (TrajectorySet)
* ``classify_nodes``      -- Jacobian-eigenvalue CP classification
* ``query_tracks``        -- filter the CPTT1 sidecar track index
                             (bbox / time range / CP type); footer-only
* ``track_read_plan``     -- directory entries one track needs
* ``decode_for_track``    -- decode ONLY the covering units and rebuild
                             the exact polyline
* ``track_summaries``     -- all per-track index summaries
* ``configure_read_devices`` / ``warm_read_path``
                          -- the devices unit decodes run on, and
                             building every decode on each of them
* ``track_aware_policy``  -- tighten-near-trajectories adaptive eb
                             policy (core.ebpolicy; DESIGN.md #16)

See DESIGN.md #9 for the sidecar index format and the seam-stitching
argument.
"""
from .adaptive import track_aware_policy, track_units  # noqa: F401
from .classify import classify_nodes  # noqa: F401
from .extraction import extract  # noqa: F401
from .index import (  # noqa: F401
    TRACK_INDEX_VERSION,
    TrackIndex,
    TrackIndexBuilder,
    parse_track_index,
)
from .model import CP_CODE, CP_TYPES, Track, TrajectorySet  # noqa: F401
from .query import (  # noqa: F401
    ContainerSource,
    TrackDecode,
    UnitCache,
    configure_read_devices,
    configure_unit_cache,
    decode_for_track,
    load_track_index,
    query_tracks,
    track_read_plan,
    track_summaries,
    unit_cache,
    warm_read_path,
)
