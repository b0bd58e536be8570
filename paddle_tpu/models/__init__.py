"""paddle_tpu.models — the five BASELINE.json model configs as Fluid-style
program builders (SURVEY.md §6: MNIST LeNet, ResNet-50, BERT-base,
Transformer NMT, DeepFM CTR).

Each module exposes ``build_*`` functions that append ops into the current
default main/startup programs (the reference builds these models the same
way in its test model scripts, e.g. unittests/dist_mnist.py,
dist_se_resnext.py, dist_transformer.py, dist_ctr.py).
"""

from . import lenet
from . import resnet
from . import bert
from . import transformer
from . import deepfm
from . import mobilenet
from . import vgg
from . import se_resnext
from . import deepseek_v3
from . import deepseek_v3_reference
from . import ouro
from . import ouro_reference
from . import lfm2_moe
from . import lfm2_moe_reference
from . import smallthinker
from . import smallthinker_reference
