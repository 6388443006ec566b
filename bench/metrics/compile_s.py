"""Seconds jax spent compiling, or reading compiled programs from the
persistent cache, during set-up (its ``backend_compile_duration`` and
``cache_retrieval_time_sec`` events)."""


def read(run):
    return run.setup_compile_s if run.setup_compile_events else None
