"""The program's compile counter (programs handed to the backend's compiler,
cache loads included) as the ``train.step`` span of the last step carries it,
less as that of step ``params["from_step"]`` does: compilations inside the
steady state, of which there should be none."""

from benchmark.harness import scope_reduce


def read(run: dict, params: dict):
    if not run.get("trace"):
        return None
    counts = {e["args"]["step"]: e["args"]["compiles"]
              for e in scope_reduce.span_events(
                  scope_reduce.program_record(), params["span"])
              if "compiles" in e.get("args", {})}
    if params["from_step"] not in counts:
        return None
    return counts[max(counts)] - counts[params["from_step"]]
