"""Device time of the client half of the split model in one PSL step:
the ops under the program's ``psl.client`` named scope
(``models/cnn.py`` ``loss_fn``: stem and stage 1, forward and backward)
in the step program's runs on the first chip, mean per run. A part of
``step_device_ms.train``. A fusion counts under its root instruction's
scope, so work fused into a client fusion (such as part of the
optimizer's update of the client's parameters) counts here. A program
without the scopes gives nothing to read."""
from chipbench import program_trace

UNIT = "ms"


def read(record):
    trace = record.get("trace")
    if record.get("kind") != "train" or trace is None or not trace.devices:
        return None
    prefix = record["step_program"]
    scopes = program_trace.load_scopes(trace, prefix)
    if scopes is None or not any(sc for prog, table in scopes.items()
                                 if prog.startswith(prefix)
                                 for sc in table.values()):
        return None
    dev = trace.devices[0]
    runs = sum(1 for name, _, _ in dev.modules if name.startswith(prefix))
    return 1e3 * program_trace.scope_time(dev, scopes, prefix,
                                          "psl.client") / runs
