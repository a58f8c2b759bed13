from raft_tla_tpu.parallel.mesh import (  # noqa: F401
    make_mesh, make_slice_mesh)

# The engines and the CP expansion load lazily — importing the package
# stays as cheap as the repo's lazy-import layering everywhere else
# assumes (ddd_shard_engine pulls utils.native: a g++ build on first use).
_LAZY = {
    "ShardCapacities": "shard_engine",
    "ShardEngine": "shard_engine",
    "check": "shard_engine",
    "reshard_checkpoint": "shard_engine",
    "DDDShardCapacities": "ddd_shard_engine",
    "DDDShardEngine": "ddd_shard_engine",
    "reshard_ddd_checkpoint": "ddd_shard_engine",
    "build_cp_expand": "cp_expand",
    "build_cp_step": "cp_expand",
    "cp_lane_count": "cp_expand",
    "cp_lane_map": "cp_expand",
}


def __getattr__(name):
    if name in _LAZY:
        import importlib
        mod = importlib.import_module(
            f"raft_tla_tpu.parallel.{_LAZY[name]}")
        return getattr(mod, name)
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")
