"""Model families: the harness loads ``families/<family>.py`` by the
configuration's ``family`` key, and the ViT family builds the program's
configuration objects each cell ran with before families existed."""
import dataclasses
import json
import pathlib

import pytest

from chipbench import harness

BENCH = pathlib.Path(__file__).resolve().parents[1]

VIT_MODEL = {
    "family": "dense", "num_layers": 12, "vocab_size": 0, "head_dim": 0,
    "rope_theta": 10000.0, "window": 0, "causal": False, "attn_every": 0,
    "moe": None, "mla": None, "ssm": None, "xlstm": None, "dec_layers": 0,
    "cross_attention": False, "frontend_embed_len": 0, "norm_eps": 1e-05,
    "act": "gelu", "tie_embeddings": False, "param_dtype": "float32",
    "compute_dtype": "bfloat16", "notes": "", "source": ""}
SSL = {"method": "moco_v3", "temperature": 0.2, "momentum": 0.99,
       "proj_dim": 256, "proj_hidden": 4096, "pred_hidden": 4096,
       "align_weight": 0.01}
TRAIN = {"optimizer": "adamw", "base_lr": 0.00015, "weight_decay": 1e-05,
         "lr_schedule": "cosine", "batch_size": 1024, "warmup_steps": 0,
         "b1": 0.9, "b2": 0.999, "eps": 1e-08, "grad_clip": 0.0,
         "remat": True, "microbatch": 0}
FL = {"num_clients": 10, "rounds": 180, "local_epochs": 3,
      "stage_allocation": "uniform", "weight_transfer": True,
      "depth_dropout": 0.0, "include_heads": True, "aux_fraction": 0.1,
      "dirichlet_beta": 0.0, "seed": 0}

# what each cell's files gave the program before the family module
CELLS = {
    ("vit-tiny", "lw_fedssl.s12.cohort8"): (
        {**VIT_MODEL, "arch_id": "vit-tiny", "d_model": 192,
         "num_heads": 3, "num_kv_heads": 3, "d_ff": 768},
        {**FL, "clients_per_round": 8, "schedule": "lw_fedssl",
         "rounds_per_stage": (0,) * 11 + (180,), "server_epochs": 3}),
    ("vit-small", "e2e.cohort3"): (
        {**VIT_MODEL, "arch_id": "vit-small", "d_model": 384,
         "num_heads": 6, "num_kv_heads": 6, "d_ff": 1536},
        {**FL, "clients_per_round": 3, "schedule": "e2e",
         "rounds_per_stage": (), "server_epochs": 0}),
}


def load(rel):
    return json.loads((BENCH / rel).read_text())


def test_a_family_is_loaded_from_its_file_by_the_configurations_key(
        tmp_path):
    vit = harness.load_family(load("configs/vit-tiny.json"))
    assert pathlib.Path(vit.__file__) == BENCH / "families" / "vit.py"
    (tmp_path / "families").mkdir()
    (tmp_path / "families" / "other.py").write_text("NAME = 'other'\n")
    other = harness.load_family({"name": "x", "family": "other"},
                                bench_dir=tmp_path)
    assert other.NAME == "other"


@pytest.mark.parametrize("family", ["nope", None])
def test_an_unknown_family_is_a_bench_error(family):
    cfg = {"name": "x"} if family is None else {"name": "x",
                                                "family": family}
    with pytest.raises(harness.BenchError, match=repr(family)):
        harness.load_family(cfg)


@pytest.mark.parametrize("config,traffic", sorted(CELLS))
def test_the_vit_familys_program_configs_are_the_cells_own(config, traffic):
    cfg = load(f"configs/{config}.json")
    tr = load(f"traffic/{traffic}.json")
    got = harness.program_configs(harness.load_family(cfg), cfg, tr)
    model, fl = CELLS[(config, traffic)]
    assert [dataclasses.asdict(o) for o in got] == [model, SSL, TRAIN, fl]


def test_the_vit_family_refuses_another_patch_size():
    cfg = load("configs/vit-tiny.json")
    cfg["patch_size"] = 8
    with pytest.raises(harness.BenchError, match="patchifies by 4"):
        harness.load_family(cfg).program_configs(
            cfg, load("traffic/e2e.cohort3.json"))
