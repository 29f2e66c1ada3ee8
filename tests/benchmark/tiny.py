"""The tests' own cells: ``BENCHMARK.json`` as it is, with every cell swapped
for a tiny one whose files live under ``tests/benchmark/``. The drivers, the
readers and the harness are the real ones: a cell is data."""

import copy

from benchmark import harness, trace_reduce

_TINY = {
    "train-gpt2m-1chip": ("train-tiny", "gpt2-tiny", "tiny-train", 1),
    "train-gpt2xl-zero-dp4":
        ("train-tiny-dp4", "gpt2-tiny-zero2", "tiny-train-dp4", 4),
    "serve-gpt2m-decode-closed":
        ("serve-tiny-closed", "gpt2-tiny", "tiny-closed", 1),
    "serve-gpt2m-chat-open": ("serve-tiny-open", "gpt2-tiny", "tiny-open", 1),
}


def manifest():
    m = copy.deepcopy(harness.load_json(harness.MANIFEST))
    m["configs"] = [
        {"name": name, "source": "tests only", "reduced": [], "why": "tests",
         "file": "tests/benchmark/configs/{}.json".format(name)}
        for name in ("gpt2-tiny", "gpt2-tiny-zero2")]
    m["workloads"] = [
        {"name": name, "config": config, "traffic": mix, "chips": chips,
         "why": "tests"} for name, config, mix, chips in _TINY.values()]
    for section in ("end_to_end", "per_layer"):
        for metric in m[section]:
            if "workloads" in metric:
                metric["workloads"] = [_TINY[w][0]
                                       for w in metric["workloads"]]
    return m


def cpu_trace_names():
    """Where the CPU backend's trace keeps what ``kernel_names.json`` finds
    on a TPU: its operations run on the host plane's XLA threads."""
    return dict(trace_reduce.kernel_names(), device_plane="^/host:CPU$",
                op_line="^tf_XLA", async_line="^$")


CPU_PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
