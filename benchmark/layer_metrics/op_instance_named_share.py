"""Of the device time that lies under any op type's scope, the share that
also lies under an op *instance's* (benchmark/reduce/op_instances.py:
the scope `fluid.executor.apply_op` opens inside the type's, named by
`fluid.executor.op_instance`).  Every operation that came from an op came
through `apply_op`, so this reads 100; less means a path some reader
splits by instance is missing time.  It reads 0 where the step program
was loaded from a compile cache that a checkout from before PR 33
filled: `op_name` is not part of the cache's key.  A program without
`op_instance` gets no value.

Prints the ten instances with the most device time (type, instance,
forward / backward / optimizer ms a step; an op and its gradient are one
line), and how many instance names more than one op of a type has.
First device, traced window, over its steps."""

from benchmark.flops import instances
from benchmark.reduce import op_instances, op_scopes

LAYER = "ops"
MOVES = "train_items_per_s"
UNIT = "%"
SOURCE = "device_trace"
SHOWN = 10


def read(run):
    steps = run.facts.get("traced_steps")
    found = op_instances.seconds(run) if run.reduced is not None else None
    if not found or not steps:
        return None
    share = op_instances.named_share(found)
    if share is None:
        return None
    both = op_instances.joined(found, op_scopes.optimizer_op_types())
    top = sorted(both.items(), key=lambda item: -sum(item[1].values()))
    print("device ms a step by op instance (forward / backward / "
          "optimizer), %d instances of %d op types: %s"
          % (len(both), len({kind for kind, _ in both}), ", ".join(
              "%s %s %.3f / %.3f / %.3f"
              % (kind, inst, ms["forward"] / steps * 1e3,
                 ms["backward"] / steps * 1e3, ms["optimizer"] / steps * 1e3)
              for (kind, inst), ms in top[:SHOWN])), flush=True)
    twice = op_instances.shared(instances.program_of(run))
    if twice:
        print("instances more than one op of a type has (they write one "
              "variable in place): %s" % ", ".join(
                  "%s %s x%d" % (kind, inst, n)
                  for (kind, inst), n in sorted(twice.items())), flush=True)
    if not share:
        print("no operation lies under an instance: the step program came "
              "from a compile cache filled by a program without them "
              "(op_name is not in the cache's key)", flush=True)
    return 100.0 * share
