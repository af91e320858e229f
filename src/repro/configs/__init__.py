"""Arch registry: importing this package registers all assigned architectures."""
from repro.configs.base import (  # noqa: F401
    SHAPES,
    ModelConfig,
    ShapeSpec,
    cell_applicable,
    get_config,
    input_specs,
    list_configs,
    reduced,
    register,
)

# one module per assigned architecture (filenames use underscores; registry
# names keep the assignment's dashes)
from repro.configs import internvl2_26b      # noqa: F401
from repro.configs import recurrentgemma_2b  # noqa: F401
from repro.configs import gemma2_2b          # noqa: F401
from repro.configs import gemma3_4b          # noqa: F401
from repro.configs import minicpm_2b         # noqa: F401
from repro.configs import nemotron_4_15b     # noqa: F401
from repro.configs import falcon_mamba_7b    # noqa: F401
from repro.configs import whisper_large_v3   # noqa: F401
from repro.configs import qwen3_moe_30b_a3b  # noqa: F401
from repro.configs import grok_1_314b        # noqa: F401
from repro.configs import moonlight_16b_a3b  # noqa: F401
from repro.configs import toy                # noqa: F401

ALL_ARCHS = list_configs()
