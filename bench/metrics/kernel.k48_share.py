"""kernel.k48_share: device time inside the k = 48 rung's Pallas kernels
(``genasm_tb_k48``, ``genasm_tail_k48``, ``genasm_dc_k48``, with XLA's
``.N`` suffix) over all device busy time in the traced window, in percent
(profiler trace: the own time of those ops, mean over the devices); 0
when none ran in the traced window."""
import re

KERNEL = re.compile(r"genasm_(tb|tail|dc)_k48(\.\d+)?$")


def read(run):
    t = run.trace
    if t is None or t.busy_s <= 0:
        return None
    k48 = sum(s for name, s in t.top_ops if KERNEL.match(name))
    return 100.0 * k48 / t.n_devices / t.busy_s
