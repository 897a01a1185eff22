"""Host spans of the serving engine, on the profiler's clock.

``span(name, **args)`` is ``jax.profiler.TraceAnnotation``: it writes
into the profiler's host plane, which shares its clock with the device
planes, so a trace taken around serving (``jax.profiler.start_trace``)
shows which host work the chip waited on.  With no profiler running a
span costs about a microsecond and records nothing.

Every name starts with ``engine.``.  Nesting inside one iteration::

    engine.iteration                  the whole of ServingEngine._step
      engine.poll                     transfer events, straggler checks
      engine.admission                slots, prefix match, restores
      engine.prefill                  the step's prefill grants
        engine.prefill_chunk          one kernel chunk (rid, start, tokens)
        engine.register_blocks        prompt blocks (rid, blocks); under
          engine.block_to_host        admission for a wholly cached prompt
                                      (one block_to_host per block copied)
      engine.decode                   the decode batch (rows, ctx_tokens)
        engine.decode_prepare         step arrays, device state (rebuilt)
        engine.decode_dispatch        the jitted decode step
        engine.decode_wait            the sync on the sampled tokens
        engine.decode_finish          tokens, prefetch plans, finish, release
      engine.cache_upkeep             page unpinning, manager tick and ageing
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation as span

ITERATION = "engine.iteration"
POLL = "engine.poll"
ADMISSION = "engine.admission"
PREFILL = "engine.prefill"
PREFILL_CHUNK = "engine.prefill_chunk"
REGISTER_BLOCKS = "engine.register_blocks"
BLOCK_TO_HOST = "engine.block_to_host"
DECODE = "engine.decode"
DECODE_PREPARE = "engine.decode_prepare"
DECODE_DISPATCH = "engine.decode_dispatch"
DECODE_WAIT = "engine.decode_wait"
DECODE_FINISH = "engine.decode_finish"
CACHE_UPKEEP = "engine.cache_upkeep"

ALL = (ITERATION, POLL, ADMISSION, PREFILL, PREFILL_CHUNK, REGISTER_BLOCKS,
       BLOCK_TO_HOST, DECODE, DECODE_PREPARE, DECODE_DISPATCH, DECODE_WAIT,
       DECODE_FINISH, CACHE_UPKEEP)
