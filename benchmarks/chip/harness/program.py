"""The system under test, as the benchmark takes it: the program's own
registry configuration, held to the configuration file's widths."""
from __future__ import annotations

_WIDTHS = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
           "n_heads": "num_attention_heads", "d_ff": "intermediate_size",
           "vocab_size": "vocab_size"}


def program_config(conf: dict):
    """The program's registry configuration, refused unless its widths are
    the configuration file's."""
    from repro.configs import get_config
    cfg = get_config(conf["registry"])
    for ours, theirs in _WIDTHS.items():
        if getattr(cfg, ours) != conf[theirs]:
            raise ValueError(f"{conf['registry']}: program {ours}="
                             f"{getattr(cfg, ours)}, configuration file "
                             f"{theirs}={conf[theirs]}")
    if cfg.dtype != conf["torch_dtype"]:
        raise ValueError(f"program serves {cfg.dtype}, configuration says "
                         f"{conf['torch_dtype']}")
    return cfg
