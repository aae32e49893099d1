"""A model built and compiled once a file: what `tests/test_olmoe.py`, `test_lfm2.py`, `test_glm4_moe_lite.py`
and `test_keye_vl2.py` share. A helper, not a test file.

`benchmark/models/<family>.py check(system, tokens)` compiles two programs of its own at every call,
`jax.jit(of_system)` and `jax.jit(of_reference)`: closures made in the call, so jax traces and compiles them
again whatever it compiled before. The benchmark calls `check` once a run; a test file called it a dozen
times on two or three systems. `benchmark/` is not a test's to edit, so the sharing is here."""

import copy
import dataclasses
from unittest import mock

import jax


class Checked:
    """`check(system, tokens, **limits)` of one benchmark module with the system's side of the comparison
    (its loss, gradient norm and routing: a program of its own) compiled and run once for a system and the
    arrays it is handed: every later call with the same ones, the negative cases' above all, reads those
    outputs and compiles its reference alone. Arrays are told apart by identity (jax's never change), so a
    system whose parameters a test replaced, or other tokens, run the program again.

    It works by standing in for `jax.jit` while `check` runs and knowing `check`'s closure by its name,
    `of_system`; a `check` that names it otherwise fails here, loudly. (A `check` that took the system's
    outputs as an argument would need none of this: ROADMAP C14, a `benchmark` issue's.)"""

    def __init__(self, bench):
        self.bench, self.kept = bench, {}

    def __call__(self, system, tokens, **limits):
        real, seen = jax.jit, []

        def jit(f, *args, **kwargs):
            if getattr(f, "__name__", "") != "of_system":
                return real(f, *args, **kwargs)
            seen.append(f)

            def once(*operands):
                key = (id(system), *map(id, jax.tree.leaves(operands)))
                if key not in self.kept:  # (what the key names is kept beside it, so that no `id` comes again)
                    self.kept[key] = (system, operands, real(f, *args, **kwargs)(*operands))
                return jax.tree.map(lambda x: x, self.kept[key][2])  # its containers anew: `check` pops from them

            return once

        with mock.patch.object(jax, "jit", jit):
            got = self.bench.check(system, tokens, **limits)
        assert len(seen) == 1, f"{self.bench.__name__}.check jits no `of_system` any more: share it another way"
        return got


def in_dtype(system, dtype):
    """`system` with its parameters kept in `dtype`: the shared system's parts, and a state of its own."""
    other = copy.copy(system)
    other.state = dataclasses.replace(system.state, params=jax.tree.map(lambda p: p.astype(dtype), system.state.params))
    return other
