"""The kinds of mixer ``cache_spec.layer_plan`` may name, each with the
record (``base.Mixer``) that answers for it. The order is that of the draws
in ``hybrid.init_params`` (a stack is drawn where its first kind stands)
and of the entries of the decode step's load vector
(``hybrid.load_names``): both are part of the accepted programs' bytes,
so a new kind goes last."""

from polyrl_tpu.models.mixers.cca import CCA
from polyrl_tpu.models.mixers.diff import CROSS, DIFF, SWA
from polyrl_tpu.models.mixers.gqa import GQA, GQA_WINDOW
from polyrl_tpu.models.mixers.kda import KDA
from polyrl_tpu.models.mixers.lightning import LIGHTNING
from polyrl_tpu.models.mixers.mamba2 import MAMBA2
from polyrl_tpu.models.mixers.mla import MLA
from polyrl_tpu.models.mixers.sparse import SPARSE
from polyrl_tpu.models.mixers.ssm import GMU, SSM, SSM_MEM

MIXERS = {m.name: m for m in (GQA, KDA, MLA, CCA, SSM, SSM_MEM, DIFF, SWA,
                              CROSS, GMU, GQA_WINDOW, SPARSE, LIGHTNING,
                              MAMBA2)}
