"""Model zoo mirroring the reference benchmark models.

reference: benchmark/fluid/models/{mnist,resnet,vgg,stacked_dynamic_lstm,
machine_translation,se_resnext}.py plus the BASELINE.json tracked set
(ResNet-50, Transformer, BERT-base, stacked LSTM, DeepFM).  Each module
exposes build_model(...) appending to the default main/startup programs
and returning the interesting vars.
"""

from . import bert  # noqa: F401
from . import decoder  # noqa: F401
from . import deepfm  # noqa: F401
from . import mnist  # noqa: F401
from . import resnet  # noqa: F401
from . import se_resnext  # noqa: F401
from . import sequence_tagging  # noqa: F401
from . import stacked_dynamic_lstm  # noqa: F401
from . import transformer  # noqa: F401
from . import vgg  # noqa: F401
