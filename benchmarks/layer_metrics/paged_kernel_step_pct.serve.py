"""Whether the decode steps run on the paged decode kernel, from the engine's
own spans: of the ``serve.decode_dispatch`` spans of the traced window, the
per cent whose field ``attn`` is ``kernel`` (the dispatched program reads K
and V in place through the block tables, ``ops/paged_attention.py``) and not
``gather`` (it builds every slot's ``max_len`` view first).  The engine
settles the field where it builds the program, by the test the model step
itself applies, so 100 says the kernel carried every step and a later change
that silently falls back reads 0.  Nothing without spans, and nothing from a
program whose dispatch spans have no such field (the parent of the PR that
added it)."""

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_span"
MOVES = "itl_p95_ms"


def read(records, trace, env):
    if records.get("kind") != "serve":
        return None
    from benchmarks.lib import spans as S
    spans = S.of_run(trace)
    if spans is None:
        return None
    told = [s.fields["attn"]
            for s in spans.named("serve.decode_dispatch", trace.window)
            if "attn" in s.fields]
    if not told:
        return None
    return 100.0 * sum(a == "kernel" for a in told) / len(told)
