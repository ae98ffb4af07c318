"""Model zoo (reference: benchmark/fluid/models/*).

Each model module exposes the reference's builder signature: a function that
constructs the program (layers only — training wiring is up to the caller)
plus a ``get_model``-style helper used by benchmarks/fluid_benchmark.py.
"""
from . import mnist  # noqa: F401
from . import vgg  # noqa: F401
from . import resnet  # noqa: F401
from . import se_resnext  # noqa: F401
from . import stacked_dynamic_lstm  # noqa: F401
from . import machine_translation  # noqa: F401
from . import transformer  # noqa: F401
from . import ocr_crnn_ctc  # noqa: F401
from . import word2vec  # noqa: F401
from . import deepfm  # noqa: F401
from . import ssd  # noqa: F401
from . import recommender  # noqa: F401
from . import label_semantic_roles  # noqa: F401
