from llm_in_practise_tpu.data.bpe import BPETokenizer, train_or_load
from llm_in_practise_tpu.data.chardata import CharTokenizer, char_lm_examples
from llm_in_practise_tpu.data.hf_tokenizer import HFTokenizerAdapter
from llm_in_practise_tpu.data.lm_dataset import (
    block_chunk,
    prepare_data,
    synthetic_corpus,
    tokenize_corpus,
    train_val_split,
)
from llm_in_practise_tpu.data.loader import batch_iterator
from llm_in_practise_tpu.data.sft import (
    IGNORE_INDEX,
    SFTBatch,
    build_sft_dataset,
    render_chatml,
    self_cognition_records,
    tokenize_for_sft,
)
from llm_in_practise_tpu.data.stream_decode import StreamDecoder

__all__ = [
    "BPETokenizer",
    "CharTokenizer",
    "HFTokenizerAdapter",
    "IGNORE_INDEX",
    "SFTBatch",
    "StreamDecoder",
    "batch_iterator",
    "block_chunk",
    "build_sft_dataset",
    "char_lm_examples",
    "prepare_data",
    "render_chatml",
    "self_cognition_records",
    "synthetic_corpus",
    "tokenize_corpus",
    "tokenize_for_sft",
    "train_or_load",
]
