"""Device milliseconds per serving step: the jit_step program's time per run."""
import measure


def read(run):
    seconds = measure.device_time(run, "jit_step", "module_s")
    count = run.trace["module_count"].get("jit_step", 0) if run.trace else 0
    return seconds / count * 1e3 if seconds and count else None
