from swiftsnails_tpu.models.word2vec import Word2VecTrainer, W2VState, sgns_loss
from swiftsnails_tpu.models.logreg import LogisticRegressionTrainer
from swiftsnails_tpu.models.fm import FMTrainer, FFMTrainer
from swiftsnails_tpu.models.widedeep import WideDeepTrainer
from swiftsnails_tpu.models.sparse_base import CTRState, SparseCTRTrainer
from swiftsnails_tpu.models.seqlm import SeqLMTrainer
from swiftsnails_tpu.models.moelm import MoELMTrainer

__all__ = [
    "Word2VecTrainer",
    "W2VState",
    "sgns_loss",
    "LogisticRegressionTrainer",
    "FMTrainer",
    "FFMTrainer",
    "WideDeepTrainer",
    "CTRState",
    "SparseCTRTrainer",
    "SeqLMTrainer",
    "MoELMTrainer",
]
