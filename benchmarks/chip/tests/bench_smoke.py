"""Small cells for the CPU tests: the smoke configurations under
``data/`` with the cells' own traffic mixes cut to test sizes."""
import json
from pathlib import Path

DATA = Path(__file__).resolve().parent / "data"

MIX_CUTS = {
    "train": {"batch": 4, "seq": 64, "ref_rows_per_block": 2},
    "decode": {"batch": 4, "prompt_len": 8, "capacity": 24, "check_rows": 2},
}
CONFIGS = {"train": "mamba2_smoke", "decode": "granite_smoke"}
CELLS = {"train": "mamba2_370m.train_2k",
         "decode": "granite_moe_1b_a400m.decode_4k"}


def ctx(kind: str, config: str = None):
    """A harness context for a smoke-sized ``train`` or ``decode`` cell,
    of its own smoke configuration or of ``config``."""
    from chipbench import harness
    from chipbench import spec as sp
    from chipbench.program import model_config
    conf = json.loads((DATA / f"{config or CONFIGS[kind]}.json").read_text())
    w = sp.workload(sp.load_spec(), CELLS[kind])
    mix = dict(sp.traffic(w["traffic"]), **MIX_CUTS[kind])
    return harness.Ctx(cell=CELLS[kind], conf=conf, model=conf["model"],
                       mix=mix, chips=w["chips"], cfg=model_config(conf))
