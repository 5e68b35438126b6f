
from .core import (  # noqa: E402
    CenterCornerPatcher,
    Convolver,
    Cropper,
    FusedConvRectifyPool,
    GrayScaler,
    ImageExtractor,
    ImageVectorizer,
    LabelExtractor,
    PixelScaler,
    Pooler,
    RandomFlipper,
    RandomImageTransformer,
    RandomPatcher,
    SymmetricRectifier,
    WindowSampler,
    Windower,
)
from .multilabel import (  # noqa: E402
    MultiLabelExtractor,
    MultiLabeledImageExtractor,
)
from .extractors import (  # noqa: E402
    BatchSIFTExtractor,
    LCSExtractor,
    SIFTExtractor,
)
from .fisher_vector import (  # noqa: E402
    EncEvalGMMFisherVectorEstimator,
    FisherVector,
    GMMFisherVectorEstimator,
    ScalaGMMFisherVectorEstimator,
)
from .daisy import DaisyExtractor  # noqa: E402
from .hog import HogExtractor  # noqa: E402
