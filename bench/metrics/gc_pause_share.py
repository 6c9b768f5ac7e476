"""Share of the window inside a garbage collection (%).

Union of the program's ``host.gc`` spans in the window over the window
(``bench/spans.py``).
"""
import spans

spans.install()


def read(run):
    s = spans.of_run(run)
    return None if s is None else 100.0 * s["gc_s"] / s["window_s"]
