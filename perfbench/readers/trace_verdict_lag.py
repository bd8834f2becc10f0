"""A percentile over the calls of the traced stretch of: the end of the
call's `devd.device_wait` annotation less the end of the kernel's program
that ran inside that call's `devd.dispatch`..`devd.device_wait`, ms: how
long the verdicts took to reach the host after the device had them. Host
annotations and device programs lie on the trace's one clock; no offset.
params: {"kernel": regex on the program's name, "q": 0..100}. Nothing
where the trace holds no such annotation (a program from before them) or
no call with its program."""

from harness import artifacts, trace_annotations
from harness.observe import quantile


def read(obs, params, device):
    if not obs.trace:
        return None
    lags = trace_annotations.verdict_lags(artifacts.read_annotations(obs),
                                          params["kernel"])
    if not lags:
        return None
    return quantile(lags, float(params["q"]) / 100.0)
