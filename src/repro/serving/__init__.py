from .service import (  # noqa: F401
    Batcher,
    BatcherConfig,
    LMScoringService,
    ScoringService,
    compute_params,
    score_tokens,
)
