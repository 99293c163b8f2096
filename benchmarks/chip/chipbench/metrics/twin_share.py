"""Engine: the clean twin's share of the device's busy time in the traced
window (ops of the ``jit_sample_clean`` module beside a served
``jit_sample_drift``), in percent. None where no drift sampler ran: a
clean cell's sampler is the clean module itself, and has no twin."""
from chipbench.attribute import DRIFT_MODULE, TWIN_MODULE


def read(run):
    s = run.scopes
    if s is None or DRIFT_MODULE not in s.by_module \
            or TWIN_MODULE not in s.by_module:
        return None
    return 100.0 * s.by_module[TWIN_MODULE] / s.busy_s
