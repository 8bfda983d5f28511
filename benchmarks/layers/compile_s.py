"""Seconds of set-up spent making the run's programs ready: the
concurrent warm-up of statements not yet in the persistent cache, plus
the one untimed pass in the timed session (re-lower, cache load).
Source: program_span (the benchmark's own span)."""


def read(run):
    total = run["spans"].total("compile")
    return total if total > 0 else None
