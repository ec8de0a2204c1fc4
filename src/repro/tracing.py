"""Names of the spans and scopes the program opens for a profiler.

Device work of a transform is tagged by stage with ``jax.named_scope``:
the name becomes part of every HLO op's ``op_name`` metadata (a TPU
profiler trace shows it as the op's ``tf_op``), and costs nothing at run
time.  Host work opens ``jax.profiler.TraceAnnotation`` spans, about a
microsecond each when no profiler runs.  A reader of a trace imports the
names from here rather than copying the strings.

Device scopes (``op_name`` segments):

* :data:`PHASE` -- ring FFTs, phase rotation, quadrature weights;
* :data:`LEGENDRE` -- the Legendre stage of every backend (the jnp scan,
  the staged and fused Pallas kernels), with the sub-scopes
  :data:`RECURRENCE` and :data:`ACCUMULATE` inside the jnp scan step;
* :data:`FOLD` -- hemisphere sums and mirrors, complex assembly and the
  layout copies between the two stages;
* :data:`EXCHANGE` -- the distributed transform's ``all_to_all`` of the
  Delta block, with the packing and unpacking of its channels (one per
  exchange chunk, every shard);
* :data:`RESHARD` -- the distributed plan's reorders around its sharded
  core: grid rings to dealt ring slots and dealt m slots back to dense
  m rows, with the moves between one device and the shards they imply.

Host spans: :data:`ALM2MAP` / :data:`MAP2ALM` around each ``Plan``
dispatch, and the serving engine's per-batch spans (:data:`ENGINE_SPANS`),
each carrying the batch's sequence number as the ``batch`` argument.
"""

from __future__ import annotations

import functools

import jax

__all__ = ["PHASE", "LEGENDRE", "FOLD", "EXCHANGE", "RESHARD", "RECURRENCE",
           "ACCUMULATE", "STAGES", "DIST_STAGES", "SUB_STAGES", "ALM2MAP",
           "MAP2ALM", "ENGINE_IDLE", "ENGINE_FORM", "ENGINE_STACK",
           "ENGINE_UPLOAD", "ENGINE_HANDOFF", "ENGINE_EXECUTE",
           "ENGINE_DOWNLOAD", "ENGINE_SCATTER", "ENGINE_SPANS", "ENGINE_HOST",
           "ENGINE_WAITS", "scoped"]

PHASE = "sht.phase"
LEGENDRE = "sht.legendre"
FOLD = "sht.fold"
EXCHANGE = "sht.exchange"
RESHARD = "sht.reshard"
RECURRENCE = "recurrence"
ACCUMULATE = "accumulate"
#: the transform's stage scopes
STAGES = (PHASE, LEGENDRE, FOLD)
#: the distributed plan's own stage scopes (a one-chip plan has neither)
DIST_STAGES = (EXCHANGE, RESHARD)
#: sub-scopes of the jnp Legendre scan step
SUB_STAGES = (RECURRENCE, ACCUMULATE)

ALM2MAP = "sht.alm2map"
MAP2ALM = "sht.map2alm"

ENGINE_IDLE = "engine.idle"          # formation waiting for work
ENGINE_FORM = "engine.form"          # pop, plan lookup, validation
ENGINE_STACK = "engine.stack"        # concatenate along K, zero pad
ENGINE_UPLOAD = "engine.upload"      # host to device, until ready
ENGINE_HANDOFF = "engine.handoff"    # formation waiting on the full slot
ENGINE_EXECUTE = "engine.execute"    # dispatch through block_until_ready
ENGINE_DOWNLOAD = "engine.download"  # device to host
ENGINE_SCATTER = "engine.scatter"    # K slices to their futures
ENGINE_SPANS = (ENGINE_IDLE, ENGINE_FORM, ENGINE_STACK, ENGINE_UPLOAD,
                ENGINE_HANDOFF, ENGINE_EXECUTE, ENGINE_DOWNLOAD,
                ENGINE_SCATTER)
#: host work on a batch outside its device execution
ENGINE_HOST = (ENGINE_FORM, ENGINE_STACK, ENGINE_UPLOAD, ENGINE_DOWNLOAD,
               ENGINE_SCATTER)
#: the engine's waits: for work, and for the execute thread
ENGINE_WAITS = (ENGINE_IDLE, ENGINE_HANDOFF)


def scoped(name: str):
    """Decorator: the function's device ops carry ``name`` (a fresh
    ``jax.named_scope`` per call, so that threads tracing at once do not
    share one)."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
