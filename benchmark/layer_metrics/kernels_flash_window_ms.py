"""Device time per step of the flash kernels that run under a window (`ops/flash_attention.py SlidingWindow`): the Mosaic
calls named `flash_*` whose `op_name` also holds a window kind's scope of `models/trinity.py` (`window`, `dense_window`),
summed inside each traced step, the median over the steps: `kernels.flash_ms` less the full layers' calls. `flash_calls(run)`
gives those calls' own scopes, which `swa.walked_over_live_blocks` reads. Nothing where no flash kernel runs under such a scope."""

from statistics import median

from benchmark.harness import program_trace, xplane
from benchmark.layer_metrics import attn_window_ms

META = {
    "name": "kernels.flash_window_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "kernels",
    "moves": "tokens_per_s_per_chip"
}


def _is_mine(program):
    def pick(op):
        if op[2] != xplane.MOSAIC_TARGET:
            return False
        parts = program.scopes.get(op[0], "").split("/")
        return (any(part.startswith(program_trace.FLASH_PREFIX) for part in parts)
                and any(kind in parts for kind in attn_window_ms.WINDOW_KINDS))
    return pick


def flash_calls(run):
    """{instruction: op_name} of the traced flash kernels under a window kind's scope."""
    program = program_trace.of(run)
    if program is None or not program.trace.devices:
        return {}
    pick = _is_mine(program)
    return {op[0]: program.scopes.get(op[0], "") for op in program.trace.devices[0]["ops"] if pick(op)}


def read(run):
    program = program_trace.of(run)
    if program is None or not program.trace.devices:
        return None
    runs = program.trace.per_step(program.trace.devices[0], _is_mine(program))
    return median(runs) / 1e6 if runs and max(runs) > 0 else None
