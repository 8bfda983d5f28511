"""Share of the chips' busy time spent in gathers: seconds of device
ops whose instruction was traced in the scope ``gather`` (every
``jnp.take`` of the trace, the exchange's send-buffer gathers among
them), summed over the chips, over the chips' busy seconds.  The time
beside ``gather_words``' count (the statement's ``kernels``).  The
scope of each op is read from its program's compiled text
(benchmarks/op_reduce.py).  Left out, never 0, where less than 95 % of
the busy time is named by an ``op.*`` scope: a program older than the
scopes, or one served from a compile cache filled by an older tree,
whose metadata it keeps: jax leaves the metadata out of the cache key
(``jax_compilation_cache_include_metadata_in_key`` is False).
Source: device_trace."""

from benchmarks import op_reduce


def read(run):
    return op_reduce.busy_pct(op_reduce.for_run(run), "gather")
