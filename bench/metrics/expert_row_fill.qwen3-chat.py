"""Expert datapath: share of the expert rows moe_gemm computes that carry a
routed token, from the program's ``decode.device`` and ``chunk.device``
spans: their ``routed_rows`` ((token, expert) pairs dispatched) over their
``computed_rows`` (capacity rows of every slot that got a token), summed
over the traced spans (%). None where the spans carry no row counts."""
from harness.program_spans import named


def read(run):
    spans = named(run, "decode.device") + named(run, "chunk.device")
    routed = sum(sp.args.get("routed_rows", 0) for sp in spans)
    computed = sum(sp.args.get("computed_rows", 0) for sp in spans)
    return 100.0 * routed / computed if computed else None
