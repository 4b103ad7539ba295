"""flops.py against a count made by hand for BERT-base, phase 1."""
from benchmark import flops

BERT_BASE = dict(seq_len=128, hidden=768, ffn_size=3072, vocab_size=30522,
                 max_preds_per_seq=20, n_layers=12)


def test_bert_base_macs_by_hand():
    # a token, a layer: q, k, v, out = 4 * 768^2 = 2,359,296;
    # ffn = 2 * 768 * 3072 = 4,718,592; scores and context over 128
    # keys = 2 * 128 * 768 = 196,608  ->  7,274,496
    per_token = 12 * 7_274_496                        # 87,293,952
    encoder = 128 * per_token                         # 11,173,625,856
    # a predicted position: transform 768^2 + decoder 768 * 30522
    mlm = 20 * (589_824 + 23_440_896)                 # 480,614,400
    nsp = 589_824 + 2 * 768                           # 591,360
    assert flops.bert_pretrain_macs_per_sample(BERT_BASE) == \
        encoder + mlm + nsp == 11_654_831_616
    per_sample = flops.bert_pretrain_flops_per_sample(BERT_BASE)
    assert per_sample == 6 * 11_654_831_616
    # 546.3 MFLOP a token; a step of 256 sequences is 17.9 TFLOP
    assert round(per_sample / 128 / 1e6, 1) == 546.3
    assert round(256 * per_sample / 1e12, 2) == 17.9


def test_kv_bytes_and_paged_reads():
    # GPT-2 medium, float32: 2 * 24 * 1024 * 4 B a cached position
    per_token = flops.kv_bytes_per_token(24, 1024, "float32")
    assert per_token == 196_608
    # contexts of 1, 16 and 17 positions hold 1, 1 and 2 pages of 16
    assert flops.decode_attention_bytes([1, 16, 17], 16, per_token) == \
        4 * 16 * 196_608
