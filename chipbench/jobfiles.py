"""The two small files every job of the benchmark is handed beside its
data: the schema, and the `.properties` file with the schema's path put in
for `{schema}`. One function for every input module."""

import json
import os
from typing import Dict, Tuple


def write_schema_and_properties(cfg: Dict, work: str) -> Tuple[str, str]:
    """Writes `schema.json` and `job.properties` of the configuration
    `cfg` under `work`; returns their paths."""
    schema_path = os.path.join(work, "schema.json")
    with open(schema_path, "w") as fh:
        json.dump(cfg["schema"], fh)
    props_path = os.path.join(work, "job.properties")
    with open(props_path, "w") as fh:
        for key, val in cfg["properties"].items():
            fh.write(f"{key}={val.format(schema=schema_path)}\n")
    return schema_path, props_path
