"""Percent of the window's jobs' run time (the ``total`` phase of
``run_job``) spent acquiring the Engine's programs (the ``build`` phase:
trace, lower, and compile or fetch from the compile cache), from the
program's own phase timer, summed over the window's jobs.  A program that
times no ``build`` phase reads nothing."""


def read(run):
    jobs = [j["phases"] for j in run.jobs if "build" in j.get("phases", {})]
    total = sum(p.get("total", 0.0) for p in jobs)
    if not total:
        return None
    return 100.0 * sum(p["build"] for p in jobs) / total
