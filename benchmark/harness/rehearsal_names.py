"""To delete, with its call in `driver.main`: the names three CPU rehearsal tests outside `paths`
still ask for. Until PR 50 a later cell's listed readings came under `<metric>.<configuration>`;
PR 50 folded those copies into the listed entries, and `tests/test_glm4_moe_lite.py:466-468`,
`tests/test_keye_vl2.py:287,290` and `tests/test_sdar.py:395-396` (seven assertions, no `benchmark`
PR's to edit) run `benchmark/run.py --rehearse-cpu` and look the old names up on its line. So a
rehearsal's line, which no driver reads, carries each listed reading under that name as well.
`driver.result_line`, which writes the chip's line, knows nothing of it. The PR that points those
assertions at `rehearsal.<metric>` deletes this file (PERF.md section 7, first row)."""

from typing import Any, Dict


def add_names_before_the_fold(line: Dict[str, Any], manifest, cell: Dict[str, Any]) -> None:
    metrics = line["metrics"]
    for entry in manifest.metrics_for(cell["name"], "per_layer"):
        value = metrics.get("rehearsal." + entry["name"])
        if "workloads" in entry and value is not None:
            metrics[f"rehearsal.{entry['name']}.{cell['config']}"] = value
