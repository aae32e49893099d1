"""The compiled step's `memory_analysis()` per device: XLA's own
`peak_memory_in_bytes`, the arguments and the most of the temporaries that
live at one time (`worker.inspect_step`; until PR 50 arguments + temporaries +
outputs - aliased, which read more than a chip holds in three cells). Says
whether a batch still fits; moves no end-to-end metric by itself."""

META = {
    "name": "device.step_hbm_gib",
    "unit": "GiB",
    "better": "lower",
    "source": "program_counter",
    "layer": "device",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return run["summary"]["compiled_step"]["step_bytes"] / 2 ** 30
