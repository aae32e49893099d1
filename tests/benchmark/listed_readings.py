"""The readings that list their cells, as the accepted benchmark holds them (PR 50; PR 56 put the
Olmo-Hybrid cell on them, PR 65 Solar-Open2's and Trinity-Mini's, PR 69 Xing4.0's): one entry and one reader file a
reading, its `workloads` every accepted cell that reports it. An entry that lists
its cells takes no later cell and only a `benchmark` PR may edit it, so a later configuration brings
such a reading as a copy, `<metric>.<configuration>` (`widened_manifest.widen()` rehearses that), and
the next `benchmark` PR folds the copies accepted since into these lists. A helper, imported by name
into the test files of the cells on the lists: a `conftest.py` here would shadow `tests/conftest.py`."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness.manifest import Manifest  # noqa: E402

MEDIUM_RESIDENT, MEDIUM_FED, XL = "gpt2-medium.resident", "gpt2-medium.fed", "gpt2-xl-fsdp4.fed"
OLMOE, LFM2, GLM = "olmoe-1b-7b-l1.fed4k", "lfm2-24b-a2b-ep8-l5.fed4k", "glm-4.7-flash-ep8-l5.fed4k"
KEYE, SDAR, OLMO_HYBRID = "keye-vl-2.0-30b-a3b-ep8.fed16k", "sdar-30b-a3b-chat-ep8.fed8k", "olmo-hybrid-7b-fsdp4.fed4k"
SOLAR, TRINITY, XING4 = "solar-open2-250b-ep40-l4.fed4k", "trinity-mini-ep16-l5.fed16k", "xing4-29b-a4b-ep8-l5.fed4k"
FED = [MEDIUM_FED, XL, OLMOE, LFM2, GLM, KEYE, SDAR, OLMO_HYBRID, SOLAR, TRINITY, XING4]
EXPERTS = [OLMOE, LFM2, GLM, KEYE, SDAR, SOLAR, TRINITY, XING4]
GANGS = [XL, OLMO_HYBRID]  # the four-chip cells: four one-chip workers joined by `jax.distributed`
TABLE = {
    **dict.fromkeys(("data.wait_ms", "host.h2d_ms", "host.report_ms", "host.report_put_ms"), FED),
    # A block pull in every window of 8 traced steps, by the mix's geometry: every second step there.
    # Not the cells whose pulls come about every eighth step (`gpt2-medium.fed` among them: a block
    # packs to 64-71 rows, so 1 window in 100 of that cell holds none): `test_benchmark_traffic.py`
    # walks the generator's blocks over twelve seeds and holds this list to what it finds.
    "data.fetch_block_ms": [LFM2],
    # Three readings at each of the clock's 8 positions clear of the traced steps: 99, 99 and 175 steps a window.
    "host.stall_pct": [MEDIUM_RESIDENT, MEDIUM_FED, OLMOE],
    **dict.fromkeys(("moe.router_ms", "moe.dispatch_ms", "moe.experts_ms", "moe.experts_roofline",
                     "moe.load_max_over_mean", "kernels.gmm_ms", "kernels.gmm_roofline"), EXPERTS),
    "step.dense_mlp_ms": [LFM2, GLM, OLMO_HYBRID, TRINITY, XING4],
    "moe.held_pairs_share": [LFM2, GLM, KEYE, SOLAR, TRINITY, XING4],  # SDAR's is 0.125 by construction: no reading
    "moe.issued_over_held": [LFM2, GLM, KEYE, SDAR, SOLAR, TRINITY, XING4],
    "moe.shared_ms": [GLM, SOLAR, TRINITY, XING4],  # the cells whose expert layers hold an expert every token meets
    "mla.latent_ms": [GLM, XING4],  # GLM's alone until PR 69: the cells whose queries, keys and values come through latents
    # What exists only across chips. `collectives.exposed_min_ms` read 0.0 in the seven one-chip cells until PR 56.
    **dict.fromkeys(("collectives.total_ms", "collectives.exposed_ms", "collectives.exposed_min_ms",
                     "entry.gang_join_s"), GANGS),
}


def holds_for(cell, listed, new):
    """`cell` is on the list of each of the `listed` readings, which are all of the table's that it
    reports; the entries in `new` list it alone; with the entries that list no cells these are all
    the cell reports. No entry of an accepted configuration comes under that configuration's name."""
    m = Manifest()
    by_name = {e["name"]: e for e in m.data["per_layer"]}
    mine = {e["name"] for e in m.metrics_for(cell, "per_layer")}
    assert set(listed) == {name for name, cells in TABLE.items() if cell in cells}
    for name in listed:
        assert by_name[name]["workloads"] == TABLE[name] and name in mine
    for name in new:
        assert by_name[name]["workloads"] == [cell] and name in mine
    unlisted = {e["name"] for e in m.data["per_layer"] if "workloads" not in e}
    assert unlisted <= mine and len(mine) == len(listed) + len(new) + len(unlisted)
    accepted = {m.cell(c)["config"] for cells in TABLE.values() for c in cells}
    assert not [n for n in by_name for c in accepted if n.endswith("." + c)]
    assert not [f for f in os.listdir(os.path.join(m.dir, "layer_metrics")) for c in accepted if c in f]  # nor a reader file
    return by_name, unlisted
