"""Compile caches: XLA compilations (or persistent-cache loads) inside
the window; 0 expected."""


def read(record, run):
    if not record.get("window"):
        return None
    return record["window_compiles"]
