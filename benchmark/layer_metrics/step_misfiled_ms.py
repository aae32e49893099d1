"""Device time per step of the fusions whose root's scope is not the scope of their work (most of their
fused products' flops, else of their fused instructions' result bytes, by the `Hlo Proto`'s `op_name`s):
what a `scope_trace.scope_ms` reading charges to the wrong metric. 0.0 where the trace was read and no
fusion is. Nothing without a raw trace of a TPU."""

from benchmark.harness import step_account

META = {
    "name": "step.misfiled_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "step",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    account = step_account.of(run)
    return account.misfiled_ms() if account else None
