from .service import Batcher, BatcherConfig, LMScoringService, ScoringService, score_tokens  # noqa: F401
