def read(window):
    """Requests that scanned something and compiled or missed the
    program cache.  (A filter that prunes every segment builds no
    program and hits no cache: not a compile.)"""
    ms = [r.metrics for r in window.requests if r.metrics is not None]
    if not ms:
        return None
    return sum(
        1 for m in ms
        if m.segments and (not m.program_cache_hit or m.compile_ms > 0)
    )
