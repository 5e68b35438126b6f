"""Pull the fitted linear model out of a pipeline the app just fitted."""
from __future__ import annotations

import numpy as np


def linear_model(pipeline):
    from keystone_tpu.nodes.learning.linear import BlockLinearMapper

    fitted = pipeline.fit()
    (mapper,) = [op for op in fitted.to_pipeline().graph.operators.values()
                 if isinstance(op, BlockLinearMapper)]
    return {"weights": np.asarray(mapper.weights),
            "feature_means": np.asarray(mapper.feature_means),
            "intercept": np.asarray(mapper.intercept)}
