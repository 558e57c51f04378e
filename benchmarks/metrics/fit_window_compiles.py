"""Compile caches: program-cache misses of the window's job plus XLA
compilations (or persistent-cache loads) inside the window; 0 expected."""


def read(record, run):
    job = record.get("job")
    if not job:
        return None
    misses = (job.get("compile_cache") or {}).get("misses", 0)
    return misses + record["window_compiles"]
