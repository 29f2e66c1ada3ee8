"""Device time inside Pallas kernels over device busy time."""


def read(run):
    trace = run["trace"]
    return 100.0 * trace["classes"].get("pallas", 0.0) / trace["busy_s"]
