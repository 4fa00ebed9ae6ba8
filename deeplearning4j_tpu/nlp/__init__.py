"""NLP / embeddings.

Reference parity: deeplearning4j-nlp-parent (SURVEY §2.5) — SequenceVectors,
Word2Vec, ParagraphVectors, GloVe, vocab construction + Huffman coding,
tokenization pipeline (sentence + document iterators, preprocessor stack),
word-vector serialization.

TPU redesign: the reference trains embeddings with N hogwild threads doing
lock-free scatter updates into shared syn0/syn1 (SURVEY §3.5) — a pattern
with no good TPU analogue. Here each step is ONE jitted computation over a
LARGE batch of (center, context, negatives) indices: embedding gathers →
sampled-softmax loss → autodiff scatter-add gradients (SURVEY §7 hard part
(c): 'redesign as large-batch sharded skipgram'). Data parallelism shards
the pair batch over the mesh like any other model. The generic trainer is
`SequenceVectors` — Word2Vec, ParagraphVectors, and DeepWalk all share it.
"""

from deeplearning4j_tpu.observe.trace import span as _span

with _span("import.nlp"):
    from deeplearning4j_tpu.nlp.vocab import VocabCache, VocabWord, build_vocab, HuffmanTree
    from deeplearning4j_tpu.nlp.tokenization import (
        AggregatingSentenceIterator, BasicLineIterator,
        CollectionSentenceIterator, CommonPreprocessor,
        DefaultTokenizerFactory, FileSentenceIterator,
        LabelAwareListSentenceIterator, LabelAwareSentenceIterator,
        LineSentenceIterator, MultipleEpochsSentenceIterator,
        PrefetchingSentenceIterator, SentenceIterator, StreamLineIterator,
    )
    from deeplearning4j_tpu.nlp.documents import (
        CollectionDocumentIterator, CollectionLabelAwareIterator,
        CompositePreProcessor, DocumentIterator, FileDocumentIterator,
        FilenamesLabelAwareIterator, FunctionPreProcessor,
        LabelAwareDocumentIterator, LabelAwareIterator, LabelledDocument,
        LabelsSource, LowCasePreProcessor, SentencePreProcessor,
        SimpleLabelAwareIterator, StripSpecialCharsPreProcessor,
    )
    from deeplearning4j_tpu.nlp.sequence_vectors import (
        AbstractSequenceIterator, CBOW, ElementsLearningAlgorithm,
        LEARNING_ALGORITHMS, Sequence, SequenceElement, SequenceVectors,
        SkipGram,
    )
    from deeplearning4j_tpu.nlp.word2vec import Word2Vec
    from deeplearning4j_tpu.nlp.paragraph_vectors import ParagraphVectors
    from deeplearning4j_tpu.nlp.glove import Glove
    from deeplearning4j_tpu.nlp.serializer import (
        write_word_vectors, read_word_vectors, write_binary, read_binary,
    )
    from deeplearning4j_tpu.nlp.bow import BagOfWordsVectorizer, TfidfVectorizer
    from deeplearning4j_tpu.nlp.stopwords import (
        StopWords, StopWordsRemovalPreprocessor,
    )

__all__ = [
    "VocabCache", "VocabWord", "build_vocab", "HuffmanTree",
    "DefaultTokenizerFactory", "CommonPreprocessor", "SentenceIterator",
    "CollectionSentenceIterator", "FileSentenceIterator",
    "LineSentenceIterator", "BasicLineIterator", "StreamLineIterator",
    "AggregatingSentenceIterator", "MultipleEpochsSentenceIterator",
    "PrefetchingSentenceIterator", "LabelAwareSentenceIterator",
    "LabelAwareListSentenceIterator",
    "StopWords", "StopWordsRemovalPreprocessor",
    "DocumentIterator", "CollectionDocumentIterator",
    "FileDocumentIterator", "LabelAwareIterator", "LabelledDocument",
    "LabelsSource", "SimpleLabelAwareIterator",
    "CollectionLabelAwareIterator", "FilenamesLabelAwareIterator",
    "LabelAwareDocumentIterator", "SentencePreProcessor",
    "LowCasePreProcessor", "StripSpecialCharsPreProcessor",
    "CompositePreProcessor", "FunctionPreProcessor",
    "SequenceVectors", "SequenceElement", "Sequence",
    "AbstractSequenceIterator", "ElementsLearningAlgorithm", "SkipGram",
    "CBOW", "LEARNING_ALGORITHMS",
    "Word2Vec", "ParagraphVectors", "Glove",
    "write_word_vectors", "read_word_vectors", "write_binary", "read_binary",
    "BagOfWordsVectorizer", "TfidfVectorizer",
]
