"""Device self time of the mesh segment module's ops under the step's
``stream`` scope (the stage that lays a shard's streamed candidates into its
output buffers), a lockstep step of the traced level, mean over the chips:
``stagered``'s ``stream`` stage from the capture ``meshred.of`` loads.
``stage_stream_ms`` is the same stage of the one-chip cells and keeps its own
list; this reader is the mesh cell's.  Nothing to read where the capture is
not a mesh's (no op names the ``exchange`` scope) or names no ``stream``
op."""

from benchmark.harness import meshred, stagered


def read(ev):
    red = meshred.of(ev)
    if not red or not red["scope_ns"]:
        return None
    return stagered.stage_ms_per_step(ev, "stream") or None
