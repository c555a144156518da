"""Host time per call of the jitted train step from ``run_training``, in ms:
the harness's span around the call, which returns once the step is enqueued
(or, when the device is behind, once the runtime takes it)."""


def read(r):
    calls = r.spans.calls.get("dispatch", 0)
    return 1e3 * r.spans.seconds["dispatch"] / calls if calls else None
