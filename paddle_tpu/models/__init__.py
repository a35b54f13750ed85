"""Framework model zoo for the BASELINE.json configs (GPT / BERT-ERNIE)."""
from .gpt import (GPTConfig, GPTModel, GPTForCausalLM, gpt_tiny, gpt_small,
                  gpt_medium, gpt_1p3b, gpt_6p7b, gpt_moe)
from .bert import (BertConfig, BertModel, BertForMaskedLM,
                   BertForSequenceClassification, ErnieModel,
                   ErnieForSequenceClassification, bert_base, ernie_base)
from .seq2seq import Seq2SeqConfig, Seq2SeqTransformer
from .decoder import (DecoderConfig, DecoderStack, DecoderForCausalLM,
                      LatentAttention, GroupedQueryAttention,
                      glm_4_7_flash_ep8, smallthinker_21b_ep8)
