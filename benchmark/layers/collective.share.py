"""The collectives' share of the devices' busy time: self time of the ops
whose HLO OPCODE is a collective's, every device, over ``busy_s``. It needs
no scope of the program's, and so checks ``step.exchange_ms``, which does
(that one reads the first device; the others also wait in the all-reduce for
the slowest shard, so this share lies above it).

``trace_reduce``'s ``collective_s`` searches an op's whole HLO text, and
counts every consumer of an all-reduce that jax named ``%psum.N`` (PR 27's
chip trace: a reshape and a fusion, 1.4 ms a step); it is printed beside."""

import re

_OPCODE = re.compile(r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
                     r"collective-permute)(-start|-done)?\(")


def read(run):
    if not run.trace or not run.trace.get("busy_s"):
        return None
    seconds = sum(s for name, s in run.trace["ops_self_s"].items()
                  if _OPCODE.search(name))
    run.say(f"collectives: {seconds:.4f}s by opcode, "
            f"{run.trace['collective_s']:.4f}s by any mention in the op's "
            f"text, of {run.trace['busy_s']:.4f}s busy a device")
    return 100.0 * seconds / run.trace["busy_s"]
