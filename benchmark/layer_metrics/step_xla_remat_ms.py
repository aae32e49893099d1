"""Device time per step of what XLA's own rematerialization pass makes a second time: the busy union, inside
the steps at the median of `step.device_ms`, of the device operations whose name holds `.remat`, the clones
`HloRematerialization` leaves where a scheduled program does not fit the chip (a product and the slices of the
stacked residuals it reads, made again just before their users). `jax.checkpoint`'s recomputation is not in it
(that is `step.recompute_ms`, by scope), and a clone in the forward loop is in this reading and not in that
one. 0.0 where the trace was read and the program holds no clone. Nothing without a raw trace of a TPU."""

from benchmark.harness import step_account

META = {
    "name": "step.xla_remat_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "step",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    account = step_account.of(run)
    return account.picked_ms(lambda name: ".remat" in name) if account else None
