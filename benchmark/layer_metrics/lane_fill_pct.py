"""How full the lane's slices were in the traced tail: the mean ``tokens``
of the ``request/slice`` instants over the mix's ``engine.prefill_chunk`` (a
prompt's last slice is as short as what is left of it); 0 where the lane
stood idle through the tail, as the engine's ``lane_fill`` reads. None for a
program that does not name the lane (no request of the tail has a wait for
it, seen or carried)."""

from benchmark import harness, scope_reduce


def read(run):
    instants = scope_reduce.of_run(run)["instants"]
    if not scope_reduce.request_gaps_ms(
            instants, "request/admitted", "request/slice", "lane_wait_ms"):
        return None
    tokens = [int(stats["tokens"])
              for _, _, stats in instants.get("request/slice", [])]
    chunk = int(run["cell"].traffic["engine"]["prefill_chunk"])
    harness.note(event="lane_fill", slices=len(tokens), tokens=tokens,
                 prefill_chunk=chunk)
    return 100.0 * sum(tokens) / (max(len(tokens), 1) * chunk)
