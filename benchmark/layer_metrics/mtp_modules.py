"""Multi-token-prediction modules of the step traced last: the ``depth`` of the
module's note (``mxnet_tpu.telemetry.plan.last("mxtpu.block.mtp")``, made where
the module's loss joins the main one).  1 in GLM-4.7-Flash's cell; 0 would say the
second loss went missing while the rate rose.  ``None`` where the program keeps no
such plan (an older program); 0 where it does and the step has no module."""


def notes(scope="mxtpu.block.mtp"):
    """The scope's last plan, ``[]`` where the step had none of it, ``None``
    where the program has no recorder or traced no step at all."""
    try:
        from mxnet_tpu.telemetry import plan
    except ImportError:
        return None
    found = plan.last(scope)
    if found is not None:
        return found
    # a step was traced if any other block noted itself
    return [] if any(plan.last("mxtpu.block." + kind) is not None
                     for kind in ("mla", "moe", "flash")) else None


def read(ctx):
    found = notes()
    return None if found is None else sum(int(n.get("depth", 0)) for n in found)
