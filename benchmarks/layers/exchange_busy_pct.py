"""Share of the chips' busy time the exchange takes, everything it does
and not the collective alone: seconds of device ops whose instruction
was traced in the scope ``exchange`` (the hash, the destination sort,
the send-buffer gathers, the ``all_to_all``) or ``replicate`` (a
sharded relation's ``all_gather``), summed over the chips, over the
chips' busy seconds (``collective_pct``'s denominator).  The TPU
compiler rewrites a tiled ``all_gather`` into slice updates and an
``all-reduce`` that carry no metadata; those are named after the data
they move, not ``replicate``, and are not counted here (at most
``collective_pct``).  The scope of
each op is read from its program's compiled text
(benchmarks/op_reduce.py).  Left out, never 0, where less than 95 % of
the busy time is named by an ``op.*`` scope: a program older than the
scopes, or one served from a compile cache filled by an older tree,
whose metadata it keeps: jax leaves the metadata out of the cache key
(``jax_compilation_cache_include_metadata_in_key`` is False).
Source: device_trace."""

from benchmarks import op_reduce


def read(run):
    return op_reduce.busy_pct(op_reduce.for_run(run), "exchange|replicate")
