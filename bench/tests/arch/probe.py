"""A probe architecture for the tests: the GQA module's arithmetic
under another name, with kernel names of its own, counting every call
that the harness makes into it."""
from collections import Counter

import harness

gqa = harness.load_arch("gqa")
calls = Counter()

DECODE_KERNEL = "probe_decode_kernel"
PREFILL_KERNEL = "probe_prefill_kernel"


def _counted(name):
    fn = getattr(gqa, name)

    def wrapped(*a, **k):
        calls[name] += 1
        return fn(*a, **k)
    return wrapped


dims = _counted("dims")
init_weights = _counted("init_weights")
logits = _counted("logits")
decode_attention = _counted("decode_attention")
prefill_attention = _counted("prefill_attention")
decode_step_flops = _counted("decode_step_flops")
prefill_chunk_flops = _counted("prefill_chunk_flops")
