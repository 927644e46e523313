"""Share of the initial parameters' bytes that the trainer's build drew on the
host, in percent: ``host_bytes`` over ``host_bytes + device_bytes`` of the
program's ``trainer.build.init_params`` span records (the attributes count what
one jitted program drew on the device under the parameters' shardings, and what
a rule that only fills a numpy array drew on the host).  ``None`` where the
program keeps no span records or the span carries no such attributes (an older
program)."""


def read(ctx):
    from mxnet_tpu.telemetry import spans
    if not hasattr(spans, "records"):
        return None
    recs = [r for r in spans.records("trainer.build.init_params", until=ctx["samples"][0][0])
            if r.attrs and "host_bytes" in r.attrs and "device_bytes" in r.attrs]
    host = sum(r.attrs["host_bytes"] for r in recs)
    total = host + sum(r.attrs["device_bytes"] for r in recs)
    return 100.0 * host / total if total else None
