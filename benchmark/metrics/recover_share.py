"""Percent of the measured window spent recovering the words' strings on
the host (``recover_from_file``): the union of the ``recover`` spans inside
the window, over the window.  The union counts a span nested in another of
the same name (the program's own and the harness's) once."""


def read(run):
    t = run.trace
    if t is None or not t.window_s:
        return None
    lo, hi = t.window
    spans = sorted((max(s, lo), min(e, hi)) for n, s, e in t.spans
                   if n == "recover" and min(e, hi) > max(s, lo))
    if not spans:
        return None
    covered, end = 0, lo
    for s, e in spans:
        covered += max(0, e - max(s, end))
        end = max(end, e)
    return 100.0 * covered / 1e9 / t.window_s
